"""The GLM-4 MoE Lite family for the benchmark: how the configuration
file (the released ``config.json`` keys, the kept layers, held experts
and rows) and a cell's job become the engine under test, what the
family's step and its kernels require in operations and bytes, and how
it is held to the plain reference in ``glm4_moe_lite_reference.py``.

From the program this takes the system under test (``Glm4MoeLiteModel``
through ``deepspeed_tpu.initialize``), the tree of its parameters, the
names of its kernels and jitted steps, and the counters its engine
accumulates; nothing of its measurement code.  The engine plumbing that
is no family's own is the GPT-2 family's, and the routing comparison is
the Laguna family's.
"""

import gc
import math
import time
import weakref

from perf.families import glm4_moe_lite_reference as reference
from perf.families import gpt2, laguna

# Names the program gives its kernels and jitted steps; the per-layer
# readers find them in the device trace by these.
FLASH_KERNELS = laguna.FLASH_KERNELS
GMM_KERNELS = laguna.GMM_KERNELS
MOE_SCOPES = laguna.MOE_SCOPES
BAND = laguna.BAND
GRAD_PROGRAM = gpt2.GRAD_PROGRAM
APPLY_PROGRAM = gpt2.APPLY_PROGRAM
ds_config = gpt2.ds_config
# the scope that holds all of the prediction module's device work, and
# the parts of ``attn`` outside the attention call itself
MTP_REGION = "mtp"
LATENT_PARTS = ("latent", "qkv", "rotary", "layout", "out")

# Parity of the engine (bf16 compute, fp32 master weights, bf16 gradient
# buffers, the router's product, scores and selection bias in float32)
# with the float32 reference on the cell's own batch (its two rows of
# 8,192 tokens, the five kept layers and the prediction module at the
# published widths, the byte budget's real plan), in the Laguna family's
# three parts (perf/families/laguna.py has the reasoning): the router's
# scores, the picks that differ for a reason other than a near tie, and
# the loss's two terms, the gradient's norm and error against the
# reference run on the PROGRAM's picks, once for the engine handed those
# picks and once, as timed_*, for the program the window times.  The
# selection biases are not left at the zeros they start from, where they
# would select nothing: the judged engine gets, from the seed, what a run
# of steps leaves (whole multiples of gamma, ``seeded_bias``), so that
# the choice by ``score + bias`` and the weights without it are compared.
# The choice is judged on the biased scores, and the bias's gradient must
# be exactly zero on both sides.
# Each limit lies between two readings on the v5e (PERF.md section 6 has
# the runs): the engine's worst over its seeds (the worse of a number and
# its timed_* namesake), and the reference itself with every product's
# operands in fp8 (e4m3, each tensor scaled to the format's range), the
# precision below the engine's, against itself in float32 on the same
# rows and picks; at the geometric middle of the two or below it.  The
# reference with bf16 products is the engine's own precision and lies
# inside every limit, as it should.
#                      engine, worst   bf16 products   fp8 scaled a tensor
#   score_err_rel        2.93e-3          1.7e-3           2.6e-2
#   picks_differ         0.033            0.018            0.262
#   picks_unexplained    1.6e-4           0                0.154
#   main / mtp loss_rel  3.9e-5 / 2.7e-5  7.5e-6 / 1.0e-5  2.5e-5 / 1.7e-5
#   grad_norm_rel        3.2e-4           6.8e-5           0.554
#   grad_err_rel         7.6e-3           4.3e-3           0.895
# (24 runs of the engine on 24 seeds, the last ten on the final tree; seed
# 2147485001 for the reference's two.)  The two loss terms do NOT tell
# the precisions apart here: a mean over 16,384 tokens of a model at its
# initial weights is nearly ln(rows) whatever the products' precision,
# and the fp8 readings lie inside the engine's own range.  LOSS_RTOL
# (two and a half times the worst of the engine's 96 readings, seven
# times their scatter about zero) therefore guards the terms themselves
# (a wrong lambda, a module that reads the wrong token, a normed rotary
# key: each moves a term by 1e-3 or more,
# tests/perf/test_glm4_moe_lite_reference.py), and fp8 is refused by the
# six other numbers.  router_err_rel reads 0.0 in every run (the product
# is float32 at the highest precision on operands the engine has already
# rounded: the Laguna family's finding); the bias's gradient is exactly
# zero on all three sides or the comparison fails.
ROUTER_RTOL = 1e-4
SCORE_RTOL = 8e-3
GAP_DELTA = 4e-3
UNEXPLAINED_MAX = 4e-3
PICK_SHARE_MAX = 0.09
LOSS_RTOL = 1e-4
GRAD_NORM_RTOL = 5e-3
GRAD_ERR_RTOL = 0.05
# the biases a judged engine is given: gamma times a whole number of
# steps up or down, at most this many
BIAS_STEPS = 32


def model_config(config, job):
    from deepspeed_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig
    if (config["tie_word_embeddings"] or config["attention_bias"]
            or config["hidden_act"] != "silu"
            or config["topk_method"] != "noaux_tc"
            or config["n_group"] != 1 or config["topk_group"] != 1
            or config["rope_scaling"] is not None
            or config["partial_rotary_factor"] != 1
            or config["num_key_value_heads"] != config["num_attention_heads"]):
        raise ValueError("the glm4_moe_lite family computes an untied head, "
                         "no attention bias, silu, a biased top-k over one "
                         "expert group, unscaled rotary over all of the "
                         "rotated dimensions and as many key heads as query "
                         "heads only")
    assumed = config["assumed"]
    return Glm4MoeLiteConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        first_k_dense_replace=config["first_k_dense_replace"],
        n_routed_experts=config["published"]["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        experts_held=(config["kept"]["experts_first"],
                      config["n_routed_experts"]),
        bias_update_rate=assumed["bias_update_rate"],
        num_nextn_predict_layers=config["num_nextn_predict_layers"],
        mtp_loss_weight=assumed["mtp_loss_weight"],
        initializer_range=assumed["initializer_range"],
        bf16=True,
        activation_checkpointing=bool(job["activation_checkpointing"]))


def build(config, job, devices, seed, rows_per_chip=None):
    """The engine of ``job`` on ``devices`` (a ``data`` mesh over all of
    them), weights made on the device from ``seed`` in one jitted call.
    ``routing_counters`` reads the routing of the engine built last."""
    global _ENGINE, _ROUTING
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.glm4_moe_lite import Glm4MoeLiteModel

    model = Glm4MoeLiteModel(model_config(config, job))
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
# the one read of its counters: they rode out of every grad program with
# the loss and were summed on the device; the read empties the
# accumulator, so it is made once, after the window, by whoever asks
# first (the harness's MFU line, ``program_memory``, a reader).
_ENGINE = None
_ROUTING = None


def routing_counters():
    """The routing summary (monitor/moe.py ``summarize_window``) of every
    step the engine built last has run, or None where there is no such
    engine or its counters are off: the Laguna family's fields, and
    ``load_max_over_mean`` (all experts), ``main_loss``, ``mtp_loss``;
    beside them ``bias_abs_max``, the largest selection bias the engine
    holds at the read."""
    global _ROUTING
    engine = _ENGINE() if _ENGINE is not None else None
    if _ROUTING is None and engine is not None:
        import jax
        from deepspeed_tpu.monitor import moe
        raw = engine._monitor_moe_stats()
        _ROUTING = moe.summarize_window(raw) if raw else None
        if _ROUTING is not None:
            _ROUTING["bias_abs_max"] = max(
                float(jax.numpy.max(jax.numpy.abs(leaf)))
                for path, leaf in jax.tree_util.tree_leaves_with_path(
                    engine.params) if _is_bias(path))
    return _ROUTING


def _is_bias(path):
    """Whether a leaf's path in the program's tree is a selection bias."""
    return [getattr(k, "key", None) for k in path][-2:] == ["moe", "bias"]


def held_share(config):
    """The share of a token's picks that landed on the held experts: the
    program's counter where the engine built last has run steps, else
    held / scored."""
    counters = routing_counters() or {}
    return counters.get("held_pick_share") or (
        config["n_routed_experts"] / config["published"]["n_routed_experts"])


def program_memory(engine, ids):
    """The GPT-2 family's account of the two step programs, and the
    routing counters beside it."""
    out = gpt2.program_memory(engine, ids)
    routing = routing_counters()
    if routing:
        out["routing"] = {k: v for k, v in routing.items()
                          if not isinstance(v, list) or len(v) <= 4}
    return out


# ---------------------------------------------------------------------- #
# what the step and its kernels require
# ---------------------------------------------------------------------- #
def attention_matrices(config):
    """Parameters of a layer's five attention matrices."""
    hid, heads = config["hidden_size"], config["num_attention_heads"]
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    return (hid * q_rank + q_rank * heads * (nope + rope)
            + hid * (kv_rank + rope) + kv_rank * heads * (nope + v)
            + heads * v * hid)


def sparse_matrices(config, share):
    """Parameters a token multiplies in a sparse FFN: the router, the
    shared expert and the experts it is ROUTED to here, ``share`` of its
    picks."""
    hid = config["hidden_size"]
    expert = 3 * hid * config["moe_intermediate_size"]
    return (hid * config["published"]["n_routed_experts"]
            + config["n_shared_experts"] * expert
            + config["num_experts_per_tok"] * share * expert)


def flops_per_token(config, job):
    """Forward plus backward FLOPs a token REQUIRES: 6 x every matrix
    entry it multiplies (the routed experts by the rows the routing sent
    here, the run's own ``held_pick_share``); the scores and values of
    each attention call over half the square; the prediction module (its
    projection, its sparse block with its attention) and the head over
    this chip's rows TWICE.  No recomputation, no tile's padding."""
    seq, hid = job["seq"], config["hidden_size"]
    layers = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], layers)
    modules = config["num_nextn_predict_layers"]
    share = held_share(config)
    blocks = layers + modules
    matrices = (blocks * attention_matrices(config)
                + dense * 3 * hid * config["intermediate_size"]
                + (blocks - dense) * sparse_matrices(config, share)
                + modules * 2 * hid * hid
                + (1 + modules) * hid * config["vocab_size"])
    dim = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    # QK^T at 256 and PV at 256: 2 products x 2 FLOPs x keys x heads x d;
    # x3 in all
    attention = blocks * 3 * 2 * 2 * ((seq + 1) / 2) * (
        config["num_attention_heads"] * dim)
    return 6 * matrices + attention


def flash_call_cost(kernel, config, job):
    """(FLOPs, bytes) one call of an attention kernel needs, counted by
    the mathematics whatever kernel implements it: 20 heads, scores at
    192 + 64 and values at 256, causal at half the square; q, k, v, the
    output and the cotangents each [B, 20, S, 256] in bf16."""
    from perf import flops
    base = kernel.replace(BAND, "")
    batch, seq = job["batch_per_chip"], job["seq"]
    heads = config["num_attention_heads"]
    dim = config["v_head_dim"]
    return (flops.flash_call_flops(base, batch, heads, seq, dim),
            flops.flash_call_bytes(base, batch, heads, seq, dim))


def gmm_call_cost(kernel, config, job, rows):
    """(operations, bytes) of ONE call of a grouped-product kernel on
    ``rows`` routed rows: the Laguna family's count at this family's
    width (an expert application is three products of 2 x rows x 2048 x
    1536 in two calls)."""
    return laguna.gmm_call_cost(
        kernel, {"hidden_size": config["hidden_size"],
                 "moe_intermediate_size": config["moe_intermediate_size"],
                 "num_experts": config["n_routed_experts"]}, job, rows)


# ---------------------------------------------------------------------- #
# parity
# ---------------------------------------------------------------------- #
def reference_spec(config):
    layers = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], layers)
    return reference.Spec(
        sparse=tuple(i >= dense for i in range(layers)),
        heads=config["num_attention_heads"],
        kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], theta=float(config["rope_theta"]),
        eps=config["rms_norm_eps"], picked=config["num_experts_per_tok"],
        scale=config["routed_scaling_factor"],
        held_first=config["kept"]["experts_first"],
        mtp_weight=config["assumed"]["mtp_loss_weight"],
        gamma=config["assumed"]["bias_update_rate"])


def reference_params(params, spec):
    """The program's parameter tree (stacked groups, its own names, fused
    gate/up matrices) under the reference's names, one entry of
    ``layers`` per kept layer and ``mtp`` for the prediction module."""
    import jax

    def gated(p):
        gate, up = jax.numpy.split(p["w1"], 2, axis=-1)
        return {"Wgate": gate, "Wup": up, "Wdown": p["w2"]}

    def one(p, sparse):
        a = p["attn"]
        out = {"norm1": p["ln1"], "norm2": p["ln2"],
               "Wqa": a["q_a"], "q_norm": a["q_norm"], "Wqb": a["q_b"],
               "Wkva": a["kv_a"], "kv_norm": a["kv_norm"],
               "Wkvb": a["kv_b"], "Wo": a["out_w"]}
        if not sparse:
            return {**out, "ffn": gated(p["ffn"])}
        return {**out, "Wr": p["moe"]["router"], "bias": p["moe"]["bias"],
                "shared": gated(p["moe"]["shared"]),
                "experts": gated(p["moe"]["experts"])}

    def unstacked(group):
        return [jax.tree.map(lambda a, i=i: a[i], group)
                for i in range(jax.tree.leaves(group)[0].shape[0])]

    stacked = [p for g in sorted(k for k in params if k.startswith("layers_"))
               for p in unstacked(params[g])]
    out = {"embed": params["wte"], "head": params["head"],
           "norm": params["ln_f"],
           "layers": [one(p, sparse)
                      for p, sparse in zip(stacked, spec.sparse)]}
    if "mtp" in params:
        m = params["mtp"]
        out["mtp"] = {"enorm": m["enorm"], "hnorm": m["hnorm"],
                      "Wp": m["proj"], "norm": m["norm"],
                      "block": one(unstacked(m["block"])[0], True)}
    return out


def gate_biases(weights):
    """[G, E] the selection biases of a ``reference_params`` tree, in
    gate order."""
    import jax.numpy as jnp
    gates = [p for p in weights["layers"] if "bias" in p]
    if "mtp" in weights:
        gates.append(weights["mtp"]["block"])
    return jnp.stack([p["bias"] for p in gates])


def seeded_bias(params, seed, gamma):
    """``params`` with every selection bias at gamma times a whole number
    in [-BIAS_STEPS, BIAS_STEPS] drawn from ``seed``: what a run of steps
    leaves there."""
    import jax

    def one(path, leaf):
        if not _is_bias(path):
            return leaf
        key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                                 len(path) + leaf.shape[0])
        steps = jax.random.randint(key, leaf.shape, -BIAS_STEPS,
                                   BIAS_STEPS + 1)
        return jax.device_put((gamma * steps).astype(leaf.dtype),
                              leaf.sharding)

    return jax.tree_util.tree_map_with_path(one, params)


def program_side(config, job, devices, seed, ids):
    """What the program gives on ``ids`` (the cell's batch, so the byte
    budget plans what it plans for the window): its scores and picks from
    the model's own forward pass in the engine's precision; the loss's
    terms and the gradients of the program the window times, which
    chooses its own top 4; the same with those picks handed in; and its
    weights.  All on the host, the engine freed."""
    import jax

    began = time.perf_counter()
    engine = build(config, job, devices, seed,
                   rows_per_chip=ids.shape[0] // len(devices))
    spec = reference_spec(config)
    model = engine.module
    engine.params = seeded_bias(engine.params, seed, spec.gamma)

    @jax.jit
    def forward(params, ids):
        # the compute-dtype copy of the weights the grad program makes,
        # the selection biases as they are stored
        cast = jax.tree_util.tree_map_with_path(
            lambda path, a: a if _is_bias(path) else a.astype(
                model.config.dtype), params)
        scores, picks, read = model.routing(cast, ids, with_inputs=True)
        # the reference's score function, in float32, on what each router
        # read: its input and its weights as the program rounded them
        as_read = reference_params(cast, spec)
        routers = [p["Wr"] for p in as_read["layers"] if "Wr" in p] + (
            [as_read["mtp"]["block"]["Wr"]] if "mtp" in as_read else [])
        with jax.default_matmul_precision("highest"):
            own = jax.numpy.stack([
                reference.router_scores(u.astype(jax.numpy.float32),
                                        w.astype(jax.numpy.float32))
                for u, w in zip(read, routers)])
        return scores, picks, laguna.rms_error(scores, own)

    def step(**forced):
        """(L, (L_main, L_mtp), gradients) of one grad program on
        ``ids``, on the host: the terms are the step's counters, the
        gradients what it handed back for this batch."""
        loss = float(engine.forward(*batch_args(ids), **forced))
        terms = engine.model_counters()
        grads = jax.device_get(reference_params(engine._cached_grads, spec))
        engine._cached_grads = None
        return loss, (terms["main_loss"], terms["mtp_loss"]), grads

    scores, picks, router_err = forward(engine.params, ids)
    out = {"scores": jax.device_get(scores),
           "router_err_rel": float(router_err)}
    del scores
    out["timed_loss"], out["timed_terms"], out["timed_grads"] = step()
    out["loss"], out["terms"], out["grads"] = step(picks=picks)
    out["picks"] = jax.device_get(picks)
    out["weights"] = jax.device_get(reference_params(engine.params, spec))
    if engine.monitor is not None:
        # its writer thread holds the engine, and so its 9.9 GB of state
        engine.monitor.close()
    del engine, model
    gc.collect()
    out["program_s"] = time.perf_counter() - began
    return out


def reference_side(program, ids, spec, device):
    """The reference's terms, gradients and scores on the program's
    picks, the rows of ``ids`` one after the other (a row's float32
    scores and its 2.8 GB of gradients are what fits): a term of the
    batch is the mean of its rows' and so are the gradients."""
    import jax
    import jax.numpy as jnp

    weights = jax.device_put(program["weights"], device)
    rows, seq = ids.shape
    picks = program["picks"].reshape(-1, rows, seq, program["picks"].shape[-1])
    # traced anew each call: the reference's small functions are looked
    # up as they stand (a test replaces one to see the comparison fail)
    one_row = jax.jit(lambda w, i, p: reference.loss_and_grads(w, i, spec, p))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    main = mtp = 0.0
    grads, scores = None, []
    for b in range(rows):
        (_, (row_main, row_mtp, row_scores, _)), row_grads = one_row(
            weights, jax.device_put(ids[b:b + 1], device),
            jax.device_put(picks[:, b], device))
        main += float(row_main) / rows
        mtp += float(row_mtp) / rows
        scores.append(row_scores)
        grads = row_grads if grads is None else add(grads, row_grads)
        del row_grads
    return ((main, mtp), jax.tree.map(lambda g: g / rows, grads),
            jnp.concatenate(scores, axis=1))


def judge(config, program, ids, device):
    """The three-part comparison of ``program_side``'s result with the
    reference on ``device``; the numbers and ``ok``."""
    import jax
    import jax.numpy as jnp

    began = time.perf_counter()
    spec = reference_spec(config)
    (ref_main, ref_mtp), ref_grads, ref_scores = reference_side(
        program, ids, spec, device)

    @jax.jit
    def compare(forced, timed, ref, scores, picks, ref_scores, bias):
        def apart(ours):
            return reference.global_norm(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b, ours, ref))
        # the choice is by score + bias, so the picks are judged there
        lifted = bias[:, None, :]
        _, differ, unexplained = laguna.routing_agreement(
            scores + lifted, picks, ref_scores + lifted, GAP_DELTA)
        bias_grads = (reference.global_norm(gate_biases(forced))
                      + reference.global_norm(gate_biases(timed))
                      + reference.global_norm(gate_biases(ref)))
        return (reference.global_norm(ref),
                reference.global_norm(forced), apart(forced),
                reference.global_norm(timed), apart(timed),
                laguna.rms_error(scores, ref_scores), differ, unexplained,
                bias_grads)

    ref_norm, norm, err, timed_norm, timed_err, score_err, differ, \
        unexplained, bias_grads = (float(x) for x in compare(
            jax.device_put(program["grads"], device),
            jax.device_put(program["timed_grads"], device), ref_grads,
            program["scores"], program["picks"], ref_scores,
            gate_biases(program["weights"])))
    main, mtp = program["terms"]
    timed_main, timed_mtp = program["timed_terms"]
    got = {"loss": program["loss"], "timed_loss": program["timed_loss"],
           "main_loss": main, "mtp_loss": mtp,
           "timed_main_loss": timed_main, "timed_mtp_loss": timed_mtp,
           "ref_main_loss": ref_main, "ref_mtp_loss": ref_mtp,
           "grad_norm": norm, "timed_grad_norm": timed_norm,
           "ref_grad_norm": ref_norm, "bias_grad_norm": bias_grads,
           "router_err_rel": program["router_err_rel"],
           "score_err_rel": score_err, "picks_differ_share": differ,
           "picks_unexplained_share": unexplained,
           "main_loss_rel": abs(main - ref_main) / ref_main,
           "mtp_loss_rel": abs(mtp - ref_mtp) / ref_mtp,
           "grad_norm_rel": abs(norm - ref_norm) / ref_norm,
           "grad_err_rel": err / ref_norm,
           "timed_main_loss_rel": abs(timed_main - ref_main) / ref_main,
           "timed_mtp_loss_rel": abs(timed_mtp - ref_mtp) / ref_mtp,
           "timed_grad_norm_rel": abs(timed_norm - ref_norm) / ref_norm,
           "timed_grad_err_rel": timed_err / ref_norm}
    # the objective the engine reports is its two counters' sum
    got["objective_rel"] = abs(
        program["loss"] - (main + spec.mtp_weight * mtp)) / program["loss"]
    limits = {"router_err_rel": ROUTER_RTOL, "score_err_rel": SCORE_RTOL,
              "picks_unexplained_share": UNEXPLAINED_MAX,
              "picks_differ_share": PICK_SHARE_MAX,
              "main_loss_rel": LOSS_RTOL, "mtp_loss_rel": LOSS_RTOL,
              "grad_norm_rel": GRAD_NORM_RTOL,
              "grad_err_rel": GRAD_ERR_RTOL,
              "timed_main_loss_rel": LOSS_RTOL,
              "timed_mtp_loss_rel": LOSS_RTOL,
              "timed_grad_norm_rel": GRAD_NORM_RTOL,
              "timed_grad_err_rel": GRAD_ERR_RTOL,
              "objective_rel": LOSS_RTOL, "bias_grad_norm": 0.0}
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
    the kept layers and the prediction module at the published widths,
    in three parts (see the limits above).  The engine's 9.9 GB of state
    and the reference's float32 weights and gradients do not share a
    chip: the engine's results go to the host and the engine is freed
    before the reference runs, row by row and layer by layer under
    ``jax.checkpoint``.  Returns the numbers and ``ok``."""
    return judge(config, program_side(config, job, devices, seed, ids), ids,
                 devices[0])
