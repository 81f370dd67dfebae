"""Peak device memory in live buffers, the fullest of the cell's chips:
the allocator's ``memory_stats()["peak_bytes_in_use"]`` after the window,
a high-water mark of the whole process.  On the v5e it leaves out the
scratch the runtime reserves for the programs (``reserved_hbm_gib``), and
``ds.initialize``'s transient copies can set it (PERF.md section 6,
PR 22)."""

LAYER, UNIT, MOVES, SOURCE = "device", "GiB", "tokens_per_s", "program_counter"


def reduce(trace, run):
    peak = run["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None
