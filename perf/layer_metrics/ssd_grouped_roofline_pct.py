"""The grouped scan's kernels' share of their roofline on the busiest
chip: ``ssd_roofline_pct``'s reduction (its ``reduce``, imported) over
this cell's family's ``ssd_call_cost``, which counts the MATHEMATICS of a
call at G groups of B and C (G products of [Q, N] x [N, Q] a chunk, B and
C G x N wide) and the configuration's own chunk of 128, whatever
implements it.  Under a name of this cell's own until a benchmark PR
appends the cell to that metric's list.  Nothing where the family has no
such count or the ``ssd_*`` kernels did not run."""

from perf.layer_metrics.ssd_roofline_pct import reduce  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"
