"""Per optimizer step, device time of latent attention around its
kernels where the heads are no whole lane tiles (192 = one and a half):
``mla_proj_ms``'s reduction (its ``reduce``, imported) over this cell's
family's ``LATENT_PARTS``.  Such heads take the XLA layout path
(``latent xla`` in the stack's log line), so part ``layout`` holds the
transposes, the broadcast of the one rotated key and the joins that
``ops/latent_layout.py``'s kernels do in one pass at whole tiles.  Under
a name of this cell's own until a benchmark PR appends the cell to that
metric's list.  Nothing where the family lists no such parts or the
program names none."""

from perf.layer_metrics.mla_proj_ms import reduce  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"
