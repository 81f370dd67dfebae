"""How uneven the routing is over ALL the experts the router scores:
the picks of the busiest expert over the mean picks an expert, averaged
over the gates (the prediction module's included) and the steps of the
run (the program's counter ``load_max_over_mean``, summed on the device
and read once after the window: the family's ``routing_counters``).  1.0
is perfectly even.  It is the quantity a selection bias acts on, and
what the busiest rank of an expert-parallel deployment would make the
others wait for; ``expert_rows_max_over_mean`` is over the held experts
alone.  Nothing where the family or the program has no such counter."""

LAYER, UNIT, MOVES, SOURCE = "model", "ratio", "step_ms_p50", (
    "program_counter")


def reduce(trace, run):
    del trace
    read = getattr(run["family"], "routing_counters", None)
    counters = read() if read else None
    return counters.get("load_max_over_mean") if counters else None
