"""Programs compiled, or fetched from the compile cache, while the
window was open.  Every shape is warmed during set-up, so this is 0 or
the run is not ``correct``."""

LAYER, UNIT, MOVES, SOURCE = "entry", "count", "tokens_per_s", "program_counter"


def reduce(trace, run):
    return float(run["compiles_in_window"])
