"""The grouped product's share of its roofline on the busiest chip where
both of an expert's widths are 2,048 and a token picks one expert:
``gmm_roofline_pct``'s reduction (its ``reduce``, imported) over this
cell's family's ``gmm_call_cost``, which counts ``2 rows k n`` at k = n =
2,048 on the rows the routing counters say the step really sent to the 8
held experts, whatever implements the product.  The row buffers hold
every pick there is (two even shares), and the tiles no group reached
are in the time and not in the count.  Under a name of this cell's own
until a benchmark PR appends the cell to that metric's list.  Nothing
where the kernels did not run (``ragged_dot`` took the product) or no
counter says how many rows were routed."""

from perf.layer_metrics.gmm_roofline_pct import reduce  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"
