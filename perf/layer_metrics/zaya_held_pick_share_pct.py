"""The share of the run's picks that landed on the experts this chip
holds, in percent, where a token picks ONE expert of 16 and 8 are held:
``held_pick_share_pct``'s reduction (its ``reduce``, imported: the
program's routing counter ``held_pick_share``, summed on the device over
every layer and step and read once after the window).  50 under a router
that favours nobody; the grouped product's rows, ``moe_top1_ms`` and
``gmm_top1_roofline_pct`` follow this number.  Under a name of this
cell's own until a benchmark PR appends the cell to that metric's list.
Nothing where the family or the program has no such counter."""

from perf.layer_metrics.held_pick_share_pct import reduce  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "%", "step_ms_p50", (
    "program_counter")
