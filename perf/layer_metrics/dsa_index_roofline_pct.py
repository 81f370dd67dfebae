"""The index-score and select kernel's share of its roofline on the
busiest chip: the least time the chip could take for the calls the
trace shows (the family's ``dsa_index_call_cost``: the causal pairs'
products over the indexer's heads, and the bytes a call must read and
write once; the select's compares and counts are no operation of the
count, so the share says how far they hold the kernel from its
products' bound) over the time the calls took.  The indexer's
projections, norm and rotation run in XLA and are outside
(``dsa_index_ms`` has their time).  Nothing where the family has no such
count or the kernel did not run."""

from perf.layer_metrics.dsa_attn_roofline_pct import share

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "step_ms_p50", "device_trace"


def reduce(trace, run):
    return share(trace, run, "DSA_INDEX_KERNELS", "dsa_index_call_cost")
