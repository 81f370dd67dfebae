"""Per optimizer step, device time of the leaf operations of the busiest
chip whose innermost named scope is one of a sparse FFN's four (the
family's ``MOE_SCOPES``: ``router``, ``dispatch``, ``experts``,
``shared``) in a program whose experts are NOT gated (moe/experts.py
``ReluSquaredExpertMLP``: two matrices of width 1,856, the shared one of
3,712), in every pass: ``moe_ms``'s reduction (its ``reduce``, imported),
under a name of this cell's own until a benchmark PR appends the cell to
that metric's list.  Nothing where the family names no such scopes or
the program's scope map names none of them."""

from perf.layer_metrics.moe_ms import reduce  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"
