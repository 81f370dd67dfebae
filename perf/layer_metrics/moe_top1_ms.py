"""Per optimizer step, device time of the leaf operations of the busiest
chip whose innermost named scope is one of a sparse FFN's (the family's
``MOE_SCOPES``: ``router``, ``dispatch``, ``experts``, ``shared``) in a
program that picks ONE expert of 16 a token by softmax and holds 8 of
them (models/zaya.py: no shared expert, the router an MLP whose time
``zaya_router_ms`` reads apart), in every pass: ``moe_ms``'s reduction
(its ``reduce``, imported), under a name of this cell's own until a
benchmark PR appends the cell to that metric's list.  Nothing where the
family names no such scopes or the program's scope map names none."""

from perf.layer_metrics.moe_ms import reduce  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "ms", "step_ms_p50", "device_trace"
