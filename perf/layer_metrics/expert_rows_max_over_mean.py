"""How uneven the routing is over the held experts: rows of the busiest
held expert over the mean rows a held expert, each averaged over the
sparse layers and the steps of the run (the program's routing counters,
summed on the device and read once after the window: the family's
``routing_counters``).  1.0 is perfectly even; the grouped product's
time follows the total, a real expert-parallel step's follows the
busiest rank.  Nothing where the family or the program has no such
counter."""

LAYER, UNIT, MOVES, SOURCE = "model", "ratio", "step_ms_p50", (
    "program_counter")


def reduce(trace, run):
    del trace
    read = getattr(run["family"], "routing_counters", None)
    counters = read() if read else None
    if not counters or not counters.get("held_rows_mean"):
        return None
    return counters["held_rows_max"] / counters["held_rows_mean"]
