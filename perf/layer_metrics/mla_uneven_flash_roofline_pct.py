"""Latent attention's kernel calls' share of their roofline where the
query/key heads and the value heads differ in size:
``mla_flash_roofline_pct``'s reduction (its ``reduce``, imported)
through this cell's family's ``flash_call_cost`` and ``FLASH_KERNELS``:
32 heads, scores at 128 + 64 and values at 128, causal at half the
square, whatever kernel implements it, so that one that pads the values
to the keys' size is charged for the padding.  Under a name of this
cell's own until a benchmark PR appends the cell to that metric's list.
Nothing where the family has no such count or the kernels did not
run."""

from perf.layer_metrics.mla_flash_roofline_pct import reduce  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"
