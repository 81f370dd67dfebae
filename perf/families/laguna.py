"""The Laguna family for the benchmark: how the configuration file (the
released ``config.json`` keys, the kept layers, held experts and rows)
and a cell's job become the engine under test, what the family's step
and its kernels require in operations and bytes, and how it is held to
the plain reference in ``laguna_reference.py``.

From the program this takes the system under test (``LagunaModel``
through ``deepspeed_tpu.initialize``), the tree of its parameters, the
names of its kernels and jitted steps, and the routing counters its
engine accumulates; nothing of its measurement code.  The engine
plumbing that is no family's own (``ds_config``, the programs' memory)
is the GPT-2 family's.
"""

import gc
import math
import time
import weakref

from perf.families import gpt2, laguna_reference as reference

# Names the program gives its kernels and jitted steps; the per-layer
# readers find them in the device trace by these.  A banded flash call's
# kernels are ``<name>_band``.  The grouped product's three kernels: rows
# times an expert's weights, the same on the transposed weights, and the
# per-expert x^T dy.
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")
GMM_KERNELS = ("gmm_rows", "gmm_rows_t", "gmm_weights")
MOE_SCOPES = ("router", "dispatch", "experts", "shared")
BAND = "_band"
GRAD_PROGRAM = gpt2.GRAD_PROGRAM
APPLY_PROGRAM = gpt2.APPLY_PROGRAM
ds_config = gpt2.ds_config

KINDS = {"full_attention": "full", "sliding_attention": "sliding"}

# Parity of the engine (bf16 compute, fp32 master weights, bf16 gradient
# buffers, the router's product in float32) with the float32 reference on
# the cell's own batch (its rows of 8,192 tokens, all five kept layers at
# the published widths, the byte budget's real plan).  A top-8 choice is
# discontinuous: where the 8th and the 9th score nearly tie, bf16
# activations flip the pick, and a flipped pick is no rounding error.  So
# the comparison has three parts and fails on any (``judge``):
#   (a) router_err_rel   rms error of the program's router scores against
#                        the reference's score function in float32 on what
#                        the program's router READ (its own arithmetic),
#       score_err_rel    and against the reference's scores on the same
#                        picks (everything upstream of the router too);
#                        the worst sparse layer, relative to the scores' rms
#   (b) a token whose picked set is not the reference's own top 8 is
#       explained if the reference's 8th and 9th scores are closer than
#       GAP_DELTA; picks_unexplained_share may differ unexplained,
#       picks_differ_share at all
#   (c) loss_rel, grad_norm_rel, grad_err_rel as for the other families,
#       against the reference run on the PROGRAM's picks: once for the
#       engine with those picks handed in (both sides then differentiate
#       one continuous function), and once, as timed_*, for the program
#       the window times, which chooses its own top 8 inside the grad
#       program and keeps them across the recomputation.
# Each limit lies between two readings on the v5e (PERF.md section 6 has
# the runs): the engine's worst over its seeds, and the reference itself
# with every product's operands in fp8 (e4m3), the precision below the
# engine's, against itself in float32 on the same rows.  "Scaled" rounds
# every operand after scaling its tensor to the format's range, and small
# cotangents vanish.  Each limit is at the geometric middle of the
# engine's worst and the NEARER fp8 reading or below it, the loss's too:
# a mean over the batch's tokens tells the precisions apart least, so its
# two readings lie closest.  router_err_rel reads 0.0 in every run: the
# product is float32 at the highest precision on operands the engine has
# already rounded to bf16, and the reference's function on the same
# operands gives the same bits; with the logits rounded to bf16 it reads
# 4.6e-4.  (A bf16 PRODUCT without that rounding gives the same scores
# bit for bit: the MXU accumulates in float32 and XLA keeps the excess
# precision.)  A dropped term (the 2.5, the gate, the window's edge, the
# partial rotation) moves several of them by far more
# (tests/perf/test_laguna_reference.py).
#                      engine, worst   fp8 scaled a tensor   fp8 cast as is
#   score_err_rel        2.70e-3           2.37e-2              4.8e-2
#   picks_differ         0.066             0.477                0.673
#   picks_unexplained    3.1e-5            0.182                0.318
#   loss_rel             3.2e-5            9.9e-5               6.8e-4
#   grad_norm_rel        3.5e-4            480                  1.58
#   grad_err_rel         5.7e-3            481                  2.33
# (11 seeds, the worse of a number and its timed_* namesake, which differ
# by a tenth at most: loss 3.06e-5 and 3.18e-5.  The reference with bf16
# products instead: 1.5e-3, 0.039, 0, 1.0e-6, 5.8e-5, 4.1e-3: the
# engine's own precision, and inside every limit.)
ROUTER_RTOL = 1e-4
SCORE_RTOL = 8e-3
GAP_DELTA = 4e-3
UNEXPLAINED_MAX = 2e-2
PICK_SHARE_MAX = 0.18
LOSS_RTOL = 6e-5
GRAD_NORM_RTOL = 1e-2
GRAD_ERR_RTOL = 0.05


def model_config(config, job):
    from deepspeed_tpu.models.laguna import LagunaConfig
    rope = config["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if (config["tie_word_embeddings"] or config["attention_bias"]
            or not config["gating"]
            or config["moe_apply_router_weight_on_input"]
            or full["rope_type"] != "yarn"
            or sliding["rope_type"] != "default"
            or sliding["partial_rotary_factor"] != 1.0):
        raise ValueError("the laguna family computes an untied head, no "
                         "attention bias, a per-head gate, the router's "
                         "weight on the output, YaRN on full layers and "
                         "default rotary on sliding ones only")
    layers = config["num_hidden_layers"]
    sparse = config["mlp_layer_types"][:layers]
    kept = config["kept"]
    return LagunaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=layers,
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"],
        rms_norm_eps=config["rms_norm_eps"],
        layer_types=tuple(config["layer_types"][:layers]),
        num_attention_heads_per_layer=tuple(
            config["num_attention_heads_per_layer"][:layers]),
        mlp_only_layers=tuple(i for i, kind in enumerate(sparse)
                              if kind == "dense"),
        num_experts=config["published"]["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config[
            "shared_expert_intermediate_size"],
        moe_routed_scaling_factor=config["moe_routed_scaling_factor"],
        experts_held=(kept["experts_first"], config["num_experts"]),
        full_rope_theta=full["rope_theta"],
        full_partial_rotary_factor=full["partial_rotary_factor"],
        yarn_factor=full["factor"],
        yarn_original_max_position_embeddings=full[
            "original_max_position_embeddings"],
        yarn_beta_fast=full["beta_fast"], yarn_beta_slow=full["beta_slow"],
        yarn_attention_factor=full["attention_factor"],
        sliding_rope_theta=sliding["rope_theta"],
        initializer_range=config["assumed"]["initializer_range"],
        bf16=True,
        activation_checkpointing=bool(job["activation_checkpointing"]))


def build(config, job, devices, seed, rows_per_chip=None):
    """The engine of ``job`` on ``devices`` (a ``data`` mesh over all of
    them), weights made on the device from ``seed`` in one jitted call.
    ``routing_counters`` reads the routing of the engine built last."""
    global _ENGINE, _ROUTING
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.laguna import LagunaModel

    model = LagunaModel(model_config(config, job))
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
# what the step and its kernels require
# ---------------------------------------------------------------------- #
def kept_layers(config):
    """[(kind, query heads, sparse)] of the kept layers."""
    n = config["num_hidden_layers"]
    return [(KINDS[kind], heads, ffn == "sparse") for kind, heads, ffn in zip(
        config["layer_types"][:n],
        config["num_attention_heads_per_layer"][:n],
        config["mlp_layer_types"][:n])]


def held_share(config):
    """The share of a token's picks that landed on the held experts: the
    program's counter where the engine built last has run steps with its
    routing counters on (``routing_counters``), else held / scored, what
    a router that favours nobody gives."""
    counters = routing_counters() or {}
    return counters.get("held_pick_share") or (
        config["num_experts"] / config["published"]["num_experts"])


def band_keys(seq, window):
    """Sum over the positions of the keys each may see: half the square
    for a causal call, the band's area under a window."""
    if not window or window >= seq:
        return seq * (seq + 1) / 2
    return window * (window + 1) / 2 + (seq - window) * window


def layer_matrices(config, heads, sparse, share):
    """Parameters of one layer's matrices a token multiplies: attention
    (q, k, v, gate, out), and the dense FFN, or the router, the shared
    expert and the experts it is ROUTED to, ``share`` of its picks
    (never the experts it is not, nor a padded tile)."""
    hid, dim = config["hidden_size"], config["head_dim"]
    attention = hid * (heads + 2 * config["num_key_value_heads"]) * dim + (
        hid * heads + heads * dim * hid)
    if not sparse:
        return attention + 3 * hid * config["intermediate_size"]
    expert = 3 * hid * config["moe_intermediate_size"]
    shared = 3 * hid * config["shared_expert_intermediate_size"]
    router = hid * config["published"]["num_experts"]
    return attention + router + shared + (
        config["num_experts_per_tok"] * share * expert)


def flops_per_token(config, job):
    """Forward plus backward FLOPs a token REQUIRES: 6 x every matrix
    entry it multiplies (the routed experts by the rows the routing sent
    here: picks a token x ``held_share``, the run's own counter); the
    scores and values of each layer over the keys its mask leaves; the
    head over this chip's rows.  No recomputation, no tile's padding."""
    seq, dim = job["seq"], config["head_dim"]
    share = held_share(config)
    matrices = attention = 0
    for kind, heads, sparse in kept_layers(config):
        matrices += layer_matrices(config, heads, sparse, share)
        keys = band_keys(seq, config["sliding_window"]
                         if kind == "sliding" else None) / seq
        # QK^T and PV: 2 products x 2 FLOPs x keys x heads x d; x3 in all
        attention += 3 * 2 * 2 * keys * heads * dim
    head = 6 * config["hidden_size"] * config["vocab_size"]
    return 6 * matrices + attention + head


def _heads_of(config, kind):
    return next(heads for k, heads, _ in kept_layers(config) if k == kind)


def flash_call_cost(kernel, config, job):
    """(FLOPs, bytes) one call of ``kernel`` (a name of the trace, with
    or without ``_band``) needs: a banded call is a sliding layer's (64
    query heads, the band's keys), a plain one a full layer's (48, half
    the square); heads of 128 on 8 key/value heads, so the key-sized
    arrays are 1/8 and 1/6 of the query-sized ones."""
    from perf import flops
    banded = kernel.endswith(BAND)
    base = kernel.replace(BAND, "")
    batch, seq, dim = job["batch_per_chip"], job["seq"], config["head_dim"]
    q_heads = _heads_of(config, "sliding" if banded else "full")
    work = (flops.FLASH_PRODUCTS[base] * 2 * batch * q_heads * dim
            * band_keys(seq, config["sliding_window"] if banded else None))
    query_sized, key_sized = {"flash_fwd": (2, 2), "flash_bwd_dkdv": (2, 4),
                              "flash_bwd_dq": (3, 2)}[base]
    moved = (query_sized * q_heads
             + key_sized * config["num_key_value_heads"]) * (
        batch * seq * dim * 2)
    return work, moved


def gmm_call_cost(kernel, config, job, rows):
    """(operations, bytes) of ONE call of a grouped-product kernel on
    ``rows`` routed rows, whatever implements the product.  An expert
    application is three products of 2 x rows x 2048 x 512 operations in
    two calls (gate and up in one, down in the other), so a call is 1.5
    products on average; a call moves its rows in and out (bf16) and the
    held experts' weights once (bf16 in the products of rows; the
    per-expert x^T dy reads two row arrays and writes the weights'
    gradient in float32)."""
    del job
    hid, ff = config["hidden_size"], config["moe_intermediate_size"]
    operations = 1.5 * 2 * rows * hid * ff
    # the two calls: [rows, hid] x [hid, 2 ff] and [rows, ff] x [ff, hid]
    row_entries = (rows * (hid + 2 * ff) + rows * (ff + hid)) / 2
    weight_entries = config["num_experts"] * 3 * hid * ff / 2
    weight_bytes = 4 if kernel == "gmm_weights" else 2
    return operations, 2 * row_entries + weight_bytes * weight_entries


# ---------------------------------------------------------------------- #
# the routing counters
# ---------------------------------------------------------------------- #
# The engine ``build`` made last (the timed one, once parity is over) and
# the one read of its routing accumulator: the counters rode out of every
# grad program with the loss and were summed on the device; the read
# empties the accumulator, so it is made once, after the window, by
# whoever asks first (the harness's MFU line, ``program_memory``).
_ENGINE = None
_ROUTING = None


def routing_counters():
    """The routing summary (monitor/moe.py ``summarize_window``) of every
    step the engine built last has run, or None where there is no such
    engine or its counters are off."""
    global _ROUTING
    engine = _ENGINE() if _ENGINE is not None else None
    if _ROUTING is None and engine is not None:
        from deepspeed_tpu.monitor import moe
        raw = engine._monitor_moe_stats()
        _ROUTING = moe.summarize_window(raw) if raw else None
    return _ROUTING


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
# parity
# ---------------------------------------------------------------------- #
def reference_spec(config):
    rope = config["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    return reference.Spec(
        layers=tuple(kept_layers(config)),
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        window=config["sliding_window"], eps=config["rms_norm_eps"],
        picked=config["num_experts_per_tok"],
        scale=config["moe_routed_scaling_factor"],
        held_first=config["kept"]["experts_first"],
        sliding_theta=float(sliding["rope_theta"]),
        full_theta=float(full["rope_theta"]),
        full_rotated=int(config["head_dim"] * full["partial_rotary_factor"]),
        yarn_factor=float(full["factor"]),
        yarn_original=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        attention_factor=full["attention_factor"])


def reference_params(params, spec):
    """The program's parameter tree (stacked groups, its own names, fused
    q/k/v and gate/up matrices) under the reference's names, one entry of
    ``layers`` per kept layer."""
    import jax

    def gated(p):
        gate, up = jax.numpy.split(p["w1"], 2, axis=-1)
        return {"Wgate": gate, "Wup": up, "Wdown": p["w2"]}

    def one(p, heads, sparse):
        q = heads * spec.head_dim
        kv = spec.kv_heads * spec.head_dim
        out = {"norm1": p["ln1"], "norm2": p["ln2"],
               "Wq": p["attn"]["qkv_w"][:, :q],
               "Wk": p["attn"]["qkv_w"][:, q:q + kv],
               "Wv": p["attn"]["qkv_w"][:, q + kv:],
               "Wg": p["attn"]["gate_w"], "Wo": p["attn"]["out_w"]}
        if not sparse:
            return {**out, "ffn": gated(p["ffn"])}
        return {**out, "Wr": p["moe"]["router"],
                "shared": gated(p["moe"]["shared"]),
                "experts": gated(p["moe"]["experts"])}

    groups = sorted(k for k in params if k.startswith("layers_"))
    stacked = [jax.tree.map(lambda a, i=i: a[i], params[g])
               for g in groups
               for i in range(jax.tree.leaves(params[g])[0].shape[0])]
    return {"embed": params["wte"], "head": params["head"],
            "norm": params["ln_f"],
            "layers": [one(p, heads, sparse) for p, (_, heads, sparse)
                       in zip(stacked, spec.layers)]}


def rms_error(scores, ref_scores):
    """The worst layer's rms of ``scores - ref_scores`` ([L, T, E]) over
    the rms of ``ref_scores``."""
    import jax.numpy as jnp
    return jnp.max(jnp.sqrt(
        jnp.mean(jnp.square(scores - ref_scores), axis=(1, 2))
        / jnp.mean(jnp.square(ref_scores), axis=(1, 2))))


def routing_agreement(scores, picks, ref_scores, delta):
    """Parts (a) and (b) of the comparison, over every sparse layer:
    ``scores`` f32 and ``picks`` int32 the program's, ``ref_scores`` the
    reference's on the same picks ([L, T, E], [L, T, k]).  Returns the
    worst layer's relative rms score error, the share of tokens whose
    picked set is not the reference's own top k, and the share that
    differs although the reference's k-th and (k+1)-th scores are at
    least ``delta`` apart."""
    import jax
    import jax.numpy as jnp
    k = picks.shape[-1]
    top, _ = jax.lax.top_k(ref_scores, k + 1)
    gap = top[..., k - 1] - top[..., k]
    ours = jnp.sum(jax.nn.one_hot(picks, scores.shape[-1], dtype=jnp.int32),
                   axis=-2)
    # the reference picks every score at or above its k-th largest
    theirs = (ref_scores >= top[..., k - 1:k]).astype(jnp.int32)
    differs = jnp.any(ours != theirs, axis=-1)
    return (rms_error(scores, ref_scores), jnp.mean(differs),
            jnp.mean(jnp.logical_and(differs, gap >= delta)))


def program_side(config, job, devices, seed, ids):
    """What the program gives on ``ids`` (the cell's batch, so the byte
    budget plans what it plans for the window): its scores and picks from
    the model's own forward pass in the engine's precision; the loss and
    gradients of the program the window times, which chooses its own top
    8; the engine's loss and gradients with those picks handed in; and
    its weights.  All on the host, the engine freed."""
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
        scores, picks, read = model.routing(cast, ids, with_inputs=True)
        # the reference's score function, in float32, on what each router
        # read: its input and its weights as the program rounded them
        routers = [layer["Wr"] for layer in reference_params(
            cast, spec)["layers"] if "Wr" in layer]
        with jax.default_matmul_precision("highest"):
            own = jax.numpy.stack([
                reference.router_scores(u.astype(jax.numpy.float32),
                                        w.astype(jax.numpy.float32))
                for u, w in zip(read, routers)])
        return scores, picks, rms_error(scores, own)

    def step(**forced):
        """(loss, gradients) of one grad program on ``ids``, on the host:
        the gradients it handed back for this batch, for which the engine
        has no public reader; two trees of them do not fit beside the
        program's peak."""
        loss = float(engine.forward(*batch_args(ids), **forced))
        grads = jax.device_get(reference_params(engine._cached_grads, spec))
        engine._cached_grads = None
        return loss, grads

    scores, picks, router_err = forward(engine.params, ids)
    out = {"scores": jax.device_get(scores),
           "router_err_rel": float(router_err)}
    del scores
    out["timed_loss"], out["timed_grads"] = step()
    out["loss"], out["grads"] = step(picks=picks)
    out["picks"] = jax.device_get(picks)
    out["weights"] = jax.device_get(reference_params(engine.params, spec))
    if engine.monitor is not None:
        # its writer thread holds the engine, and so its 9.7 GB of state
        engine.monitor.close()
    del engine, model
    gc.collect()
    out["program_s"] = time.perf_counter() - began
    return out


def reference_side(program, ids, spec, device):
    """The reference's loss, gradients and scores on the program's picks,
    the rows of ``ids`` one after the other (a row's float32 scores and
    its 2.8 GB of gradients are what fits): the batch's loss is the mean
    of its rows' and so are the gradients."""
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
    loss, grads, scores = 0.0, None, []
    for b in range(rows):
        (row_loss, (row_scores, _)), row_grads = one_row(
            weights, jax.device_put(ids[b:b + 1], device),
            jax.device_put(picks[:, b], device))
        loss += float(row_loss) / rows
        scores.append(row_scores)
        grads = row_grads if grads is None else add(grads, row_grads)
        del row_grads
    return (loss, jax.tree.map(lambda g: g / rows, grads),
            jnp.concatenate(scores, axis=1))


def judge(config, program, ids, device):
    """The three-part comparison of ``program_side``'s result with the
    reference on ``device``; the numbers and ``ok``."""
    import jax
    import jax.numpy as jnp

    began = time.perf_counter()
    ref_loss, ref_grads, ref_scores = reference_side(
        program, ids, reference_spec(config), device)

    @jax.jit
    def compare(forced, timed, ref, scores, picks, ref_scores):
        def apart(ours):
            return reference.global_norm(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b, ours, ref))
        return (reference.global_norm(ref),
                reference.global_norm(forced), apart(forced),
                reference.global_norm(timed), apart(timed),
                *routing_agreement(scores, picks, ref_scores, GAP_DELTA))

    ref_norm, norm, err, timed_norm, timed_err, score_err, differ, \
        unexplained = (float(x) for x in compare(
            jax.device_put(program["grads"], device),
            jax.device_put(program["timed_grads"], device), ref_grads,
            program["scores"], program["picks"], ref_scores))
    got = {"loss": program["loss"], "timed_loss": program["timed_loss"],
           "ref_loss": ref_loss, "grad_norm": norm,
           "timed_grad_norm": timed_norm, "ref_grad_norm": ref_norm,
           "router_err_rel": program["router_err_rel"],
           "score_err_rel": score_err, "picks_differ_share": differ,
           "picks_unexplained_share": unexplained,
           "loss_rel": abs(program["loss"] - ref_loss) / ref_loss,
           "grad_norm_rel": abs(norm - ref_norm) / ref_norm,
           "grad_err_rel": err / ref_norm,
           "timed_loss_rel": abs(program["timed_loss"] - ref_loss) / ref_loss,
           "timed_grad_norm_rel": abs(timed_norm - ref_norm) / ref_norm,
           "timed_grad_err_rel": timed_err / ref_norm}
    limits = {"router_err_rel": ROUTER_RTOL, "score_err_rel": SCORE_RTOL,
              "picks_unexplained_share": UNEXPLAINED_MAX,
              "picks_differ_share": PICK_SHARE_MAX,
              "loss_rel": LOSS_RTOL, "grad_norm_rel": GRAD_NORM_RTOL,
              "grad_err_rel": GRAD_ERR_RTOL, "timed_loss_rel": LOSS_RTOL,
              "timed_grad_norm_rel": GRAD_NORM_RTOL,
              "timed_grad_err_rel": GRAD_ERR_RTOL}
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
    all the kept layers at the published widths, in three parts (see the
    limits above).  The program's scores and picks come from the model's
    own forward pass in the engine's precision; loss and gradients are
    taken twice, from the program the window times (its own top 8) and
    with those picks handed in, and the reference's with the same picks,
    so that it and the second differentiate one continuous function.
    The engine's 9.7 GB of state and the reference's float32 weights and
    gradients do not share a chip: the engine's results go to the host
    and the engine is freed before the reference runs, row by row and
    layer by layer under ``jax.checkpoint``.  Returns the numbers and
    ``ok``."""
    return judge(config, program_side(config, job, devices, seed, ids), ids,
                 devices[0])
