"""Operations and bytes the algorithms REQUIRE, computed from shapes.

These are the numerators of every utilization the benchmark reports, so
they count what the mathematics needs and not what a program happens to
execute: causal attention at half of the full score matrix, the
vocabulary projection in, the embedding look-up out, recomputation not
counted.  (The program's own ``GPT2Config.flops_per_token`` counts
attention in full, 5.7% more at GPT-2 large and S=1,024.)
"""


def decoder_train_flops_per_token(hidden, layers, seq, vocab,
                                  intermediate=None, causal=True):
    """Forward plus backward FLOPs per token of a dense pre-LN decoder
    with a (tied or untied) vocabulary projection.

    6 x (parameters outside the embeddings): every weight is used once
    forward (2 FLOPs) and twice backward.  Attention adds, per layer and
    token, the score and value products over the keys it may see: S keys
    in full, S/2 on average under a causal mask.  The head is a real
    [*, h] x [h, V] product over the published vocabulary rows.
    """
    inter = 4 * hidden if intermediate is None else intermediate
    per_layer = (4 * hidden * hidden + 2 * hidden * inter  # the matrices
                 + 4 * hidden + inter + hidden             # their biases
                 + 4 * hidden)                             # two LayerNorms
    outside_embeddings = layers * per_layer + 2 * hidden   # + final LN
    keys = seq / 2 if causal else seq
    # QK^T and PV: 2 products x 2 FLOPs x keys x hidden, forward; x3 in all
    attention = 3 * 2 * 2 * keys * hidden * layers
    head = 6 * hidden * vocab
    return 6 * outside_embeddings + attention + head


# [S, S] products each flash kernel performs in one call (it is handed
# q, k, v, dO and the row statistics only, so the backward kernels
# recompute the scores, and both of them need dP):
#   flash_fwd       QK^T, PV
#   flash_bwd_dkdv  QK^T, dO V^T, P^T dO (dV), dS^T Q (dK)
#   flash_bwd_dq    QK^T, dO V^T, dS K (dQ)
FLASH_PRODUCTS = {"flash_fwd": 2, "flash_bwd_dkdv": 4, "flash_bwd_dq": 3}
# [B, H, S, D] arrays each call reads and writes (the [B, H, S] row
# statistics are 1/D of one and left out)
FLASH_ARRAYS = {"flash_fwd": 4, "flash_bwd_dkdv": 7, "flash_bwd_dq": 6}


def flash_call_flops(kernel, batch, heads, seq, head_dim, causal=True):
    """FLOPs one call of ``kernel`` needs on [batch, heads, seq, head_dim]
    operands: 2 x S x S x D per product, half of it under a causal mask.
    The in-kernel dropout bits are not counted (no FLOP of the algorithm)."""
    full = 2 * batch * heads * seq * seq * head_dim
    return FLASH_PRODUCTS[kernel] * (full / 2 if causal else full)


def flash_call_bytes(kernel, batch, heads, seq, head_dim, itemsize=2):
    """Bytes one call must move between HBM and the core."""
    return FLASH_ARRAYS[kernel] * batch * heads * seq * head_dim * itemsize


def roofline_seconds(flops, nbytes, peak):
    """(least seconds the chip could take, which bound sets it)."""
    compute = flops / peak["bf16_flops"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
