"""The grouped product's share of its roofline on the busiest chip where
the experts' width is no whole number of lane tiles (1,856 = 14.5 x 128):
``gmm_roofline_pct``'s reduction (its ``reduce``, imported) over this
cell's family's ``gmm_call_cost``, which counts ``2 rows k n`` at the
PUBLISHED width on the rows the routing counters give, whatever
implements the product and however it cuts its blocks.  The ``gmm_*``
calls on buffers that hold few routed rows (one rank of sixteen: the
walk's first chunk runs whatever the counts) are in the time and not in
the count.  Under a name of this cell's own until a benchmark PR appends
the cell to that metric's list.  Nothing where the kernels did not run
(``ragged_dot`` took the product) or no counter says how many rows were
routed."""

from perf.layer_metrics.gmm_roofline_pct import reduce  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"
