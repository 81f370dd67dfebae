"""The flash kernels' share of their roofline on the busiest chip where
the calls are compressed convolutional attention's (8 query heads on 2
key/value heads of 128 at 8,192 positions, queries and keys of unit norm):
``mla_flash_roofline_pct``'s reduction (its ``reduce``, imported) over
this cell's family's ``flash_call_cost``, which counts the mathematics of
a call, causal at half the square, the key/value arrays at their own two
heads, whatever kernel implements it.  Under a name of this cell's own
until a benchmark PR appends the cell to a shared metric's list.  Nothing
where the family has no such count or the kernels did not run."""

from perf.layer_metrics.mla_flash_roofline_pct import reduce  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"
