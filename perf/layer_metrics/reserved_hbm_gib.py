"""The most device memory the runtime reserved for the programs' own
scratch (XLA's temporaries), the fullest chip: the allocator's
``memory_stats()["peak_bytes_reserved"]``.  It sits beside what
``peak_hbm_gib`` counts, not inside it, and it is the number that grows
with the batch and with a lighter recomputation policy (PERF.md
section 6, PR 22)."""

LAYER, UNIT, MOVES, SOURCE = "device", "GiB", "tokens_per_s", "program_counter"


def reduce(trace, run):
    reserved = max((s.get("peak_bytes_reserved", 0)
                    for s in run["memory_stats"]), default=0)
    return reserved / 2 ** 30 if reserved else None
