"""The share of the run's picks that landed on the experts this chip
holds, in percent: the program's routing counter ``held_pick_share``
(summed on the device over every gate and step, read once after the
window: the family's ``routing_counters``).  8 of 128 experts get 6.25
under a router that favours nobody; on one rank of sixteen a router can
learn to send its tokens to the absent experts, and the grouped
product's rows, ``moe_relu2_ms`` and ``gmm_uneven_roofline_pct`` follow
this number.  Nothing where the family or the program has no such
counter."""

LAYER, UNIT, MOVES, SOURCE = "model", "%", "step_ms_p50", (
    "program_counter")


def reduce(trace, run):
    del trace
    read = getattr(run["family"], "routing_counters", None)
    counters = read() if read else None
    share = counters.get("held_pick_share") if counters else None
    return None if share is None else 100.0 * share
