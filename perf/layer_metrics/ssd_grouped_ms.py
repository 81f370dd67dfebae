"""Per optimizer step, device time of the leaf operations of the busiest
chip whose innermost named scope is ``ssm`` in a program whose Mamba-2
mixers read SEVERAL groups of B and C (models/nemotron_h.py: 8 groups, a
head block of the scan's kernels a group, chunks of 128): the
projections, the conv, the grouped scan and the grouped gated norm, every
part and every pass.  ``mamba2_ms``'s reduction (its ``reduce``,
imported), under a name of this cell's own until a benchmark PR appends
the cell to that metric's list.  Nothing where the program gives no scope
map or names no such scope."""

from perf.layer_metrics.mamba2_ms import reduce  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"
