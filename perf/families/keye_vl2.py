"""The Keye-VL-2.0 family for the benchmark: how the configuration file
(the released ``config.json`` keys, the kept layers, held experts and
rows) and a cell's job become the engine under test, what the family's
step and its kernels require in operations and bytes, and how it is held
to the plain reference in ``keye_vl2_reference.py``.

From the program this takes the system under test (``KeyeVL2Model``
through ``deepspeed_tpu.initialize``), the tree of its parameters, the
names of its kernels and jitted steps, and the counters its engine
accumulates; nothing of its measurement code.  The engine plumbing that
is no family's own is the GPT-2 family's, and the routing comparison is
the Laguna family's.

What the cell's four readers (``perf/layer_metrics/dsa_*.py``) divide
by.  ``dsa_attn_call_cost``: the three restricted-attention kernels by
the SELECTED pairs, ``sum_t min(t + 1, topk)`` a head, whatever the
kernel computes (today's compute every tile up to the diagonal, 4.3
times that).  ``dsa_index_call_cost``:
``dsa_select`` (index scores and the select in one kernel) by the causal
pairs' 16 x 64 multiply-adds, and no operation for the select (compares
and counts have no place in a FLOP count: a share of that bound says how
far the 32 counting passes hold the kernel from the products').  OUTSIDE
both, in XLA, under scope parts ``index`` and ``select``: the indexer's
three projections, its LayerNorm and its rotation; the alignment kernel
``dsa_align`` is part ``align``'s alone.
"""

import gc
import math
import time
import weakref

from perf.families import gpt2, keye_vl2_reference as reference, laguna

# Names the program gives its kernels and jitted steps; per-layer readers
# find them in the device trace by these.  No plain flash kernel runs in
# this family.
FLASH_KERNELS = laguna.FLASH_KERNELS
GMM_KERNELS = laguna.GMM_KERNELS
MOE_SCOPES = ("router", "dispatch", "experts")
DSA_ATTN_KERNELS = ("dsa_attn_fwd", "dsa_attn_bwd_dq", "dsa_attn_bwd_dkdv")
DSA_INDEX_KERNELS = ("dsa_select",)
# the parts of scope ``attn`` that ``dsa_index_ms`` and ``dsa_align_ms`` read
DSA_INDEX_PARTS = ("index", "select")
DSA_ALIGN_PARTS = ("align",)
GRAD_PROGRAM = gpt2.GRAD_PROGRAM
APPLY_PROGRAM = gpt2.APPLY_PROGRAM
ds_config = gpt2.ds_config

# Parity of the engine (bf16 compute, fp32 master weights, bf16 gradient
# buffers, the router's product in float32, the index scores summed in
# float32 from bf16 operands) with the float32 reference on the cell's
# own row of 16,384 tokens, the six kept layers at the published widths,
# the byte budget's real plan.  Two choices are discontinuous here, the
# router's top 8 and the indexer's top 2,048, so the comparison is the
# Laguna family's three parts with the selection beside the picks
# (``judge`` fails on any):
#   (a) router_err_rel, score_err_rel: the router's scores, as there
#   (b) picks_differ_share / picks_unexplained_share: as there;
#       select_differ_share: pairs on which the reference's OWN selection
#       (float32 scores, ``lax.top_k``) and the program's differ, over the
#       pairs kept; select_unexplained_share: those of them whose float32
#       score lies farther from the query's k-th largest than SELECT_DELTA
#       times the rms of the query's scores: bf16 operands cannot move a
#       score that far, a wrong scale, rotation or norm can
#   (c) main_loss_rel, index_loss_rel, grad_norm_rel, grad_err_rel against
#       the reference run on the PROGRAM's picks and selection, once for
#       the engine with both handed in and once, as timed_*, for the
#       program the window times, which makes both choices inside the
#       grad program.  The indexer's leaves are judged APART
#       (index_grad_*: their gradient is a hundredth of the whole and
#       would hide in it), relative to their own norm.
# kept_share must read the arithmetic's share on every side.
# Each limit lies between two readings on the v5e (PERF.md section 6 has
# the runs): the engine's worst over its seeds (the worse of a number and
# its timed_* namesake, which agree to the sixth digit: the window's
# program made the choices it was then handed), and the reference itself
# with every product's operands in fp8 (e4m3, each tensor scaled to the
# format's range), the precision below the engine's, against itself in
# float32 on the same row, picks and selection (seed 2147485001); at the
# geometric middle of the two or below it.
#                         engine, worst of 12 seeds   fp8 scaled a tensor
#   score_err_rel            6.1e-3                     6.0e-2
#   picks_differ             0.045                      0.368
#   picks_unexplained        3.7e-3  (GAP_DELTA 2e-4)   0.269
#   select_differ            0.0095                     0.095
#   select_unexplained       3.3e-5                     5.8e-2
#     (at 0.01 / 0.1 / 0.3 of the rms instead of SELECT_DELTA's 0.03:
#     1.6e-3 / 0 / 0 and 8.1e-2 / 1.6e-2 / 2.4e-4)
#   main / index loss_rel    8.9e-5 / 5.4e-4            3.9e-4 / 7.7e-3
#   grad_norm_rel            4.2e-4                     0.999
#   grad_err_rel             5.7e-3                     1.000
#   index_grad_norm_rel      3.7e-3                     0.995
#   index_grad_err_rel       1.4e-2                     1.000
# (under fp8 scaled a tensor the small cotangents vanish, so the gradients
# read as all error, the Laguna family's finding.)  router_err_rel reads
# 0.0 in every run and kept_share_err 4e-10 (the float32 counter's last
# digit).  The main loss tells the precisions apart least, as in the other
# families: its limit is at the two readings' geometric middle.  The
# reference on its OWN picks and selection read 10.162842 / 0.402876
# beside 10.162782 / 0.402906 on the program's and the engine's 10.162642
# / 0.402783 (main / index loss, seed 2147485001).
ROUTER_RTOL = 1e-4
SCORE_RTOL = 1.5e-2
GAP_DELTA = 2e-4           # softmax scores of 128: about 1 / 40 of a pick's
PICK_UNEXPLAINED_MAX = 2e-2
PICK_SHARE_MAX = 0.12
SELECT_DELTA = 0.03        # of the rms of a query's index scores
SELECT_SHARE_MAX = 0.03
SELECT_UNEXPLAINED_MAX = 1.5e-3
LOSS_RTOL = 2e-4
INDEX_LOSS_RTOL = 2e-3
GRAD_NORM_RTOL = 5e-3
GRAD_ERR_RTOL = 0.05
INDEX_GRAD_NORM_RTOL = 0.03
INDEX_GRAD_ERR_RTOL = 0.08
KEPT_SHARE_ATOL = 1e-6
# queries a block of the reference's attention on the chip
REFERENCE_BLOCK = 256


def model_config(config, job):
    from deepspeed_tpu.models.keye_vl2 import KeyeVL2Config
    rope, sa = config["rope_scaling"], config["sa_config"]
    if (config["tie_word_embeddings"] or config["attention_bias"]
            or config["hidden_act"] != "silu"
            or config["use_sliding_window"] or config["mlp_only_layers"]
            or config["decoder_sparse_step"] != 1
            or rope["rope_type"] != "default"
            or 2 * sum(rope["mrope_section"]) != config["head_dim"]
            or sa["indexer_num_kv_heads"] != 1
            or config["num_experts"] != config["num_local_experts"]):
        raise ValueError("the keye_vl2 family computes an untied head, no "
                         "attention bias, silu, no sliding window, an "
                         "expert layer everywhere, unscaled rotary whose "
                         "sections cover the head, and an indexer with "
                         "one key head only")
    assumed = config["assumed"]
    return KeyeVL2Config(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        num_experts=config["published"]["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        experts_held=(config["kept"]["experts_first"],
                      config["num_experts"]),
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"],
        indexer_norm_eps=assumed["indexer_norm_eps"],
        index_loss_weight=assumed["index_loss_weight"],
        initializer_range=assumed["initializer_range"],
        bf16=True,
        activation_checkpointing=bool(job["activation_checkpointing"]))


def build(config, job, devices, seed, rows_per_chip=None):
    """The engine of ``job`` on ``devices`` (a ``data`` mesh over all of
    them), weights made on the device from ``seed`` in one jitted call.
    ``routing_counters`` reads the counters of the engine built last."""
    global _ENGINE, _ROUTING
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.keye_vl2 import KeyeVL2Model

    model = KeyeVL2Model(model_config(config, job))
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=devices, data=len(devices))
    params = jax.jit(model.init_params)(jax.random.PRNGKey(seed))
    rows = job["batch_per_chip"] if rows_per_chip is None else rows_per_chip
    engine, _, _, _ = ds.initialize(
        model=model, mesh=mesh, model_parameters=params,
        config=ds_config(job, len(devices), rows))
    _ENGINE, _ROUTING = weakref.ref(engine), None
    return engine


def batch_args(ids):
    """What ``engine.forward`` takes for one step's token ids."""
    return (ids,)


def vocab_rows(config):
    """Rows of the vocabulary traffic may draw: this chip's share."""
    return config["vocab_size"]


# ---------------------------------------------------------------------- #
# the counters
# ---------------------------------------------------------------------- #
# The engine ``build`` made last (the timed one, once parity is over) and
# the one read of its counters (the Laguna family's note).
_ENGINE = None
_ROUTING = None


def routing_counters():
    """The routing summary (monitor/moe.py ``summarize_window``) of every
    step the engine built last has run, or None; beside the Laguna
    family's fields it carries the model's own counters, averaged over
    those steps: ``main_loss``, ``index_loss``, ``kept_share``."""
    global _ROUTING
    engine = _ENGINE() if _ENGINE is not None else None
    if _ROUTING is None and engine is not None:
        from deepspeed_tpu.monitor import moe
        raw = engine._monitor_moe_stats()
        _ROUTING = moe.summarize_window(raw) if raw else None
    return _ROUTING


def held_share(config):
    """The share of a token's picks that landed on the held experts: the
    program's counter where the engine built last has run steps, else
    held / scored."""
    counters = routing_counters() or {}
    return counters.get("held_pick_share") or (
        config["num_experts"] / config["published"]["num_experts"])


def program_memory(engine, ids):
    """The GPT-2 family's account of the two step programs, and the
    counters beside it."""
    out = gpt2.program_memory(engine, ids)
    routing = routing_counters()
    if routing:
        out["routing"] = {k: v for k, v in routing.items()
                          if not isinstance(v, list) or len(v) <= 4}
    return out


# ---------------------------------------------------------------------- #
# what the step and its kernels require
# ---------------------------------------------------------------------- #
def causal_pairs(seq):
    """Pairs (t, s) with s <= t."""
    return seq * (seq + 1) // 2


def selected_pairs(seq, topk):
    """``sum_t min(t + 1, topk)``: the pairs a head's attention needs."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def kept_share(config, job):
    """Selected over causal pairs: what the program's counter must read."""
    return selected_pairs(job["seq"], config["sa_config"]["topk"]) / (
        causal_pairs(job["seq"]))


def layer_matrices(config, share):
    """Parameters of one layer's matrices a token multiplies: attention
    (q, k, v, out), the indexer's three, the router, and the experts it
    is ROUTED to here, ``share`` of its picks."""
    hid, dim = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    sa = config["sa_config"]
    attention = hid * (heads + 2 * kv) * dim + heads * dim * hid
    indexer = hid * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                     + sa["indexer_head_dim"] + sa["indexer_num_heads"])
    router = hid * config["published"]["num_experts"]
    expert = 3 * hid * config["moe_intermediate_size"]
    return attention + indexer + router + (
        config["num_experts_per_tok"] * share * expert)


def flops_per_token(config, job):
    """Forward plus backward FLOPs a token REQUIRES: 6 x every matrix
    entry it multiplies (the routed experts by the rows the routing sent
    here, the run's own ``held_pick_share``); the restricted attention's
    scores and values on the SELECTED pairs; the indexer's products on
    the causal pairs forward and, for the alignment term's gradient, on
    the selected pairs twice backward; the head over this chip's rows.
    The alignment term's pass over the main attention's scores is not
    counted: a program that kept the probabilities would not make it.
    No recomputation, no tile's padding."""
    seq, sa = job["seq"], config["sa_config"]
    layers = config["num_hidden_layers"]
    selected = selected_pairs(seq, sa["topk"]) / seq
    # QK^T and PV: 2 products x 2 FLOPs x keys x heads x d; x3 in all
    attention = 3 * 2 * 2 * selected * (
        config["num_attention_heads"] * config["head_dim"])
    index = 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * (
        causal_pairs(seq) / seq + 2 * selected)
    head = 6 * config["hidden_size"] * config["vocab_size"]
    return (6 * layers * layer_matrices(config, held_share(config))
            + layers * (attention + index) + head)


# [S, S]-shaped products each restricted-attention kernel needs, as the
# flash kernels' (perf/flops.py FLASH_PRODUCTS), and the query-sized and
# key-sized arrays it moves
DSA_PRODUCTS = {"dsa_attn_fwd": 2, "dsa_attn_bwd_dkdv": 4,
                "dsa_attn_bwd_dq": 3}
DSA_ARRAYS = {"dsa_attn_fwd": (2, 2), "dsa_attn_bwd_dkdv": (2, 4),
              "dsa_attn_bwd_dq": (3, 2)}


def keep_bytes(job):
    """Bytes of a row's packed keep-set, a bit a pair."""
    return job["batch_per_chip"] * job["seq"] * job["seq"] // 8


def dsa_attn_call_cost(kernel, config, job):
    """(FLOPs, bytes) one call of a restricted-attention kernel needs,
    by the mathematics: the selected pairs of 32 heads with scores and
    values at 128; q-sized arrays of 32 heads and key-sized ones of 4 in
    bf16, and the packed keep-set once."""
    batch, seq, dim = job["batch_per_chip"], job["seq"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    work = DSA_PRODUCTS[kernel] * 2 * batch * heads * dim * selected_pairs(
        seq, config["sa_config"]["topk"])
    query_sized, key_sized = DSA_ARRAYS[kernel]
    moved = (query_sized * heads + key_sized * kv) * (
        batch * seq * dim * 2) + keep_bytes(job)
    return work, moved


def dsa_index_call_cost(kernel, config, job):
    """(operations, bytes) one call of ``dsa_select`` needs: the causal
    pairs' products over 16 heads of 64 (the select itself is compares
    and counts, no FLOP); qI, kI and w read and the packed keep-set and
    a row statistic written, once."""
    del kernel
    batch, seq, sa = job["batch_per_chip"], job["seq"], config["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    work = 2 * batch * causal_pairs(seq) * heads * dim
    moved = batch * seq * (heads * dim * 2 + dim * 2 + heads * 4 + 4) + (
        keep_bytes(job))
    return work, moved


def gmm_call_cost(kernel, config, job, rows):
    """(operations, bytes) of ONE call of a grouped-product kernel on
    ``rows`` routed rows: the Laguna family's count at this family's
    width (an expert application is three products of 2 x rows x 2048 x
    768 in two calls)."""
    return laguna.gmm_call_cost(kernel, config, job, rows)


# ---------------------------------------------------------------------- #
# parity
# ---------------------------------------------------------------------- #
def reference_spec(config, block=REFERENCE_BLOCK):
    sa = config["sa_config"]
    return reference.Spec(
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
        picked=config["num_experts_per_tok"],
        renormalize=config["norm_topk_prob"],
        held_first=config["kept"]["experts_first"],
        idx_heads=sa["indexer_num_heads"], idx_dim=sa["indexer_head_dim"],
        topk=sa["topk"], idx_eps=config["assumed"]["indexer_norm_eps"],
        index_weight=config["assumed"]["index_loss_weight"],
        block=block, gap_delta=SELECT_DELTA)


def reference_params(params, spec):
    """The program's parameter tree (one stacked group, its own names,
    fused q/k/v and gate/up matrices) under the reference's names, one
    entry of ``layers`` per kept layer."""
    import jax

    def one(p):
        q = spec.heads * spec.head_dim
        kv = spec.kv_heads * spec.head_dim
        gate, up = jax.numpy.split(p["moe"]["experts"]["w1"], 2, axis=-1)
        a, i = p["attn"], p["indexer"]
        return {"norm1": p["ln1"], "norm2": p["ln2"],
                "Wq": a["qkv_w"][:, :q], "Wk": a["qkv_w"][:, q:q + kv],
                "Wv": a["qkv_w"][:, q + kv:], "q_norm": a["q_norm"],
                "k_norm": a["k_norm"], "Wo": a["out_w"],
                "WqI": i["q_w"], "WkI": i["k_w"],
                "kI_norm_w": i["k_norm_w"], "kI_norm_b": i["k_norm_b"],
                "Ww": i["w_w"], "Wr": p["moe"]["router"],
                "experts": {"Wgate": gate, "Wup": up,
                            "Wdown": p["moe"]["experts"]["w2"]}}

    return {"embed": params["wte"], "head": params["head"],
            "norm": params["ln_f"],
            "layers": [one(jax.tree.map(lambda a, i=i: a[i],
                                        params["layers"]))
                       for i in range(spec.layers)]}


INDEXER_LEAVES = ("WqI", "WkI", "kI_norm_w", "kI_norm_b", "Ww")


def split_indexer(tree):
    """(the indexer's leaves of a ``reference_params`` tree, the rest)."""
    indexer = [{k: p[k] for k in INDEXER_LEAVES} for p in tree["layers"]]
    rest = {**tree, "layers": [
        {k: v for k, v in p.items() if k not in INDEXER_LEAVES}
        for p in tree["layers"]]}
    return indexer, rest


def program_side(config, job, devices, seed, ids):
    """What the program gives on ``ids`` (the cell's batch, so the byte
    budget plans what it plans for the window): its router scores, picks
    and packed keep-sets from the model's own forward pass in the
    engine's precision; the loss's terms and the gradients of the program
    the window times, which makes both choices itself; the same with
    picks and keep-sets handed in; and its weights.  All on the host, the
    engine freed."""
    import jax

    began = time.perf_counter()
    engine = build(config, job, devices, seed,
                   rows_per_chip=ids.shape[0] // len(devices))
    spec = reference_spec(config)
    model = engine.module

    @jax.jit
    def forward(params, ids):
        # the compute-dtype copy of the weights the grad program makes
        cast = jax.tree.map(lambda a: a.astype(model.config.dtype), params)
        scores, picks, read, keep = model.routing(cast, ids,
                                                  with_inputs=True)
        routers = [p["Wr"] for p in reference_params(cast, spec)["layers"]]
        with jax.default_matmul_precision("highest"):
            own = jax.numpy.stack([
                reference.router_probs(u.astype(jax.numpy.float32),
                                       w.astype(jax.numpy.float32))
                for u, w in zip(read, routers)])
        return scores, picks, keep, laguna.rms_error(scores, own)

    def step(**forced):
        """(L, the step's counters, gradients) of one grad program on
        ``ids``, on the host."""
        loss = float(engine.forward(*batch_args(ids), **forced))
        terms = engine.model_counters()
        grads = jax.device_get(reference_params(engine._cached_grads, spec))
        engine._cached_grads = None
        return loss, terms, grads

    scores, picks, keep, router_err = forward(engine.params, ids)
    out = {"scores": jax.device_get(scores),
           "router_err_rel": float(router_err)}
    del scores
    out["timed_loss"], out["timed_terms"], out["timed_grads"] = step()
    out["loss"], out["terms"], out["grads"] = step(picks=picks, keep=keep)
    out["picks"] = jax.device_get(picks)
    out["keep"] = jax.device_get(keep)
    out["weights"] = jax.device_get(reference_params(engine.params, spec))
    if engine.monitor is not None:
        # its writer thread holds the engine, and so its 9.2 GB of state
        engine.monitor.close()
    del engine, model, picks, keep
    gc.collect()
    out["program_s"] = time.perf_counter() - began
    return out


def reference_side(program, ids, spec, device, forced=True):
    """The reference's terms, gradients, router probabilities, own picks
    and selection counts, the rows of ``ids`` one after the other (a
    term of the batch is the mean of its rows' and so are the
    gradients); ``forced``: on the program's picks and keep-sets, else on
    its own choices."""
    import jax
    import jax.numpy as jnp

    weights = jax.device_put(program["weights"], device)
    rows, seq = ids.shape
    picks = program["picks"].reshape(spec.layers, rows, seq, -1)
    keep = program["keep"]                      # [L, rows, S / 32, S]
    # traced anew each call: the reference's small functions are looked
    # up as they stand (a test replaces one to see the comparison fail)
    one_row = jax.jit(lambda w, i, p, k: reference.loss_and_grads(
        w, i, spec, p, k))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    main = index = 0.0
    grads, probs, counts = None, [], 0.0
    for b in range(rows):
        (_, (row_main, row_index, row_probs, _, row_counts)), row_grads = (
            one_row(weights, jax.device_put(ids[b:b + 1], device),
                    jax.device_put(picks[:, b], device) if forced else None,
                    jax.device_put(keep[:, b], device) if forced else None))
        main += float(row_main) / rows
        index += float(row_index) / rows
        probs.append(row_probs)
        counts = counts + row_counts
        grads = row_grads if grads is None else add(grads, row_grads)
        del row_grads
    return ((main, index), jax.tree.map(lambda g: g / rows, grads),
            jnp.concatenate(probs, axis=1), jax.device_get(counts))


def judge(config, job, program, ids, device):
    """The three-part comparison of ``program_side``'s result with the
    reference on ``device``; the numbers and ``ok``."""
    import jax
    import jax.numpy as jnp

    began = time.perf_counter()
    spec = reference_spec(config)
    (ref_main, ref_index), ref_grads, ref_scores, counts = reference_side(
        program, ids, spec, device)

    @jax.jit
    def compare(forced, timed, ref, scores, picks, ref_scores):
        ref_idx, ref_rest = split_indexer(ref)

        def against(ours):
            idx, rest = split_indexer(jax.tree.map(
                lambda a: a.astype(jnp.float32), ours))
            apart = [reference.global_norm(jax.tree.map(
                lambda a, b: a - b, mine, theirs))
                for mine, theirs in ((rest, ref_rest), (idx, ref_idx))]
            return (reference.global_norm(rest), apart[0],
                    reference.global_norm(idx), apart[1])
        return (reference.global_norm(ref_rest),
                reference.global_norm(ref_idx), *against(forced),
                *against(timed),
                *laguna.routing_agreement(scores, picks, ref_scores,
                                          GAP_DELTA))

    (ref_norm, ref_idx_norm, norm, err, idx_norm, idx_err, timed_norm,
     timed_err, timed_idx_norm, timed_idx_err, score_err, differ,
     unexplained) = (float(x) for x in compare(
         jax.device_put(program["grads"], device),
         jax.device_put(program["timed_grads"], device), ref_grads,
         program["scores"], program["picks"], ref_scores))
    terms, timed = program["terms"], program["timed_terms"]
    kept = counts[:, 0].sum()
    got = {"loss": program["loss"], "timed_loss": program["timed_loss"],
           "main_loss": terms["main_loss"],
           "index_loss": terms["index_loss"],
           "timed_main_loss": timed["main_loss"],
           "timed_index_loss": timed["index_loss"],
           "ref_main_loss": ref_main, "ref_index_loss": ref_index,
           "kept_share": terms["kept_share"],
           "timed_kept_share": timed["kept_share"],
           "grad_norm": norm, "timed_grad_norm": timed_norm,
           "ref_grad_norm": ref_norm, "index_grad_norm": idx_norm,
           "timed_index_grad_norm": timed_idx_norm,
           "ref_index_grad_norm": ref_idx_norm,
           "router_err_rel": program["router_err_rel"],
           "score_err_rel": score_err, "picks_differ_share": differ,
           "picks_unexplained_share": unexplained,
           "select_differ_share": float(counts[:, 1].sum() / kept),
           "select_unexplained_share": float(counts[:, 2].sum() / kept),
           "main_loss_rel": abs(terms["main_loss"] - ref_main) / ref_main,
           "index_loss_rel": abs(terms["index_loss"] - ref_index)
           / ref_index,
           "grad_norm_rel": abs(norm - ref_norm) / ref_norm,
           "grad_err_rel": err / ref_norm,
           "index_grad_norm_rel": abs(idx_norm - ref_idx_norm)
           / ref_idx_norm,
           "index_grad_err_rel": idx_err / ref_idx_norm,
           "timed_main_loss_rel": abs(timed["main_loss"] - ref_main)
           / ref_main,
           "timed_index_loss_rel": abs(timed["index_loss"] - ref_index)
           / ref_index,
           "timed_grad_norm_rel": abs(timed_norm - ref_norm) / ref_norm,
           "timed_grad_err_rel": timed_err / ref_norm,
           "timed_index_grad_norm_rel": abs(timed_idx_norm - ref_idx_norm)
           / ref_idx_norm,
           "timed_index_grad_err_rel": timed_idx_err / ref_idx_norm}
    # the objective the engine reports is its two counters' sum, and the
    # selection kept exactly what the arithmetic says on both programs
    got["objective_rel"] = abs(program["loss"] - (
        terms["main_loss"] + spec.index_weight * terms["index_loss"])
        ) / program["loss"]
    share = kept_share(config, job)
    got["kept_share_err"] = max(abs(terms["kept_share"] - share),
                                abs(timed["kept_share"] - share),
                                abs(kept / (spec.layers * ids.shape[0]
                                            * causal_pairs(ids.shape[1]))
                                    - share))
    limits = {"router_err_rel": ROUTER_RTOL, "score_err_rel": SCORE_RTOL,
              "picks_unexplained_share": PICK_UNEXPLAINED_MAX,
              "picks_differ_share": PICK_SHARE_MAX,
              "select_differ_share": SELECT_SHARE_MAX,
              "select_unexplained_share": SELECT_UNEXPLAINED_MAX,
              "kept_share_err": KEPT_SHARE_ATOL,
              "objective_rel": LOSS_RTOL}
    for prefix in ("", "timed_"):
        limits.update({
            prefix + "main_loss_rel": LOSS_RTOL,
            prefix + "index_loss_rel": INDEX_LOSS_RTOL,
            prefix + "grad_norm_rel": GRAD_NORM_RTOL,
            prefix + "grad_err_rel": GRAD_ERR_RTOL,
            prefix + "index_grad_norm_rel": INDEX_GRAD_NORM_RTOL,
            prefix + "index_grad_err_rel": INDEX_GRAD_ERR_RTOL})
    got["failed"] = [name for name, limit in limits.items()
                     if not got[name] <= limit]
    got["ok"] = bool(math.isfinite(got["loss"])
                     and math.isfinite(got["timed_loss"])
                     and not got["failed"])
    got["seconds"] = {"program": round(program.get("program_s", 0.0), 1),
                      "reference": round(time.perf_counter() - began, 1)}
    return got


def parity(config, job, devices, seed, ids):
    """Engine against reference on ``ids`` (the cell's batch, [rows, S]),
    the kept layers at the published widths, in three parts (see the
    limits above).  The engine's 9.2 GB of state and the reference's
    float32 weights and gradients do not share a chip: the engine's
    results go to the host and the engine is freed before the reference
    runs, a block of queries at a time and layer by layer under
    ``jax.checkpoint``.  Returns the numbers and ``ok``."""
    return judge(config, job, program_side(config, job, devices, seed, ids),
                 ids, devices[0])
