"""Model FLOP/s utilization: tokens per second of this run's untraced
steps times the FLOPs a token requires (perf/flops.py: causal attention
at half, the head in, recomputation not counted) over chips times the
published bf16 peak.  ``tokens_per_s`` times a constant of the cell."""

LAYER, UNIT, MOVES, SOURCE = "entry", "%", "tokens_per_s", "host_clock"


def reduce(trace, run):
    need = run["family"].flops_per_token(run["config"], run["job"])
    return (100.0 * run["host"]["tokens_per_s"] * need
            / (run["chips"] * run["peak"]["bf16_flops"]))
