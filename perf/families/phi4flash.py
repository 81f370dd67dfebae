"""The Phi-4-mini-flash (SambaY) family for the benchmark: how the
configuration file (the released ``config.json`` keys, the kept layers
and rows) and a cell's job become the engine under test, what the
family's step and its kernels require in operations and bytes, and how
it is held to the plain reference in ``phi4flash_reference.py``.

From the program this takes the system under test (``Phi4FlashModel``
through ``deepspeed_tpu.initialize``), the tree of its parameters and
the names of its kernels and jitted steps; nothing of its measurement
code.  The engine plumbing that is no family's own (``ds_config``,
``program_memory``) is the GPT-2 family's.
"""

import gc
import math

from perf.families import gpt2, phi4flash_reference as reference

# Names the program gives its kernels and jitted steps; the per-layer
# readers find them in the device trace by these.  A banded call's
# kernels are ``<name>_band``, which the plain names match too.
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")
SSCAN_KERNELS = ("sscan_fwd", "sscan_bwd")
BAND = "_band"
GRAD_PROGRAM = gpt2.GRAD_PROGRAM
APPLY_PROGRAM = gpt2.APPLY_PROGRAM
ds_config = gpt2.ds_config
program_memory = gpt2.program_memory

# Parity of the engine (bf16 compute, fp32 master weights, bf16 gradient
# buffers) with the float32 reference on ONE row of 8,192 tokens, the
# timed shape, all six kept layers at the published widths.  Three
# numbers, each relative to the reference's, as for GPT-2
# (perf/families/gpt2.py has what each catches).  Each limit lies
# between two readings on the v5e (my chip runs, PR 34; PERF.md section
# 6): the engine's worst over 11 seeds, and the reference itself with
# every product's operands in fp8 (e4m3), the precision below the
# engine's, against itself in float32 on the same row:
#                engine, worst   fp8 scaled a tensor   fp8 cast as is
#   loss           6.1e-5            1.3e-4               5.3e-4
#   grad_norm      2.5e-4            1.9e-3               0.83
#   grad_err       0.032             0.32                 0.99
# (the reference with bf16 products instead: 1.1e-5, 6.7e-5, 0.020, the
# engine's own).  "Scaled" rounds forward and backward operands after
# scaling each tensor to the format's range, as an fp8 recipe does; "as
# is" casts them, and small cotangents vanish.  The gradient limits lie
# between the engine and the scaled reading and refuse it (0.1 is their
# geometric middle; 1e-3 is four times the engine's worst and half the
# reading); the loss, a mean over 8,191 tokens, tells the two apart
# least, and its limit lies between the engine and the plain cast.  A
# dropped term (the (1 - lam0), the Dskip term, the band) moves all
# three by far more (tests/perf/test_phi4flash_reference.py).
LOSS_RTOL = 1.5e-4
GRAD_NORM_RTOL = 1e-3
GRAD_ERR_RTOL = 0.1

KINDS = {"mamba": "mamba", "attn": "window", "mamba+memory": "mamba_mem",
         "attn+kv": "full", "gmu": "gmu", "cross": "cross"}


def model_config(config, job):
    from deepspeed_tpu.models.phi4flash import Phi4FlashConfig
    if config["hidden_act"] != "silu" or not config["tie_word_embeddings"]:
        raise ValueError("the phi4flash family computes a silu-gated FFN "
                         "and a tied head only")
    kept = config["kept"]
    return Phi4FlashConfig(
        vocab_size=config["assumed"]["vocab_rows_padded"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["published"]["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        sliding_window=config["sliding_window"],
        mb_per_layer=config["mb_per_layer"],
        layer_norm_eps=config["layer_norm_eps"],
        ssm_state=config["assumed"]["ssm_state"],
        ssm_conv=config["assumed"]["ssm_conv"],
        ssm_expand=config["assumed"]["ssm_expand"],
        dt_rank=config["assumed"]["dt_rank"],
        self_pairs=kept["self_pairs"], cross_pairs=kept["cross_pairs"],
        bf16=True,
        activation_checkpointing=bool(job["activation_checkpointing"]))


def build(config, job, devices, seed, rows_per_chip=None):
    """The engine of ``job`` on ``devices`` (a ``data`` mesh over all of
    them), weights made on the device from ``seed`` in one jitted call."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.phi4flash import Phi4FlashModel

    model = Phi4FlashModel(model_config(config, job))
    if len(model.config.layer_plan()) != config["num_hidden_layers"]:
        raise ValueError("the kept pairs do not make num_hidden_layers")
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=devices, data=len(devices))
    params = jax.jit(model.init_params)(jax.random.PRNGKey(seed))
    rows = job["batch_per_chip"] if rows_per_chip is None else rows_per_chip
    engine, _, _, _ = ds.initialize(
        model=model, mesh=mesh, model_parameters=params,
        config=ds_config(job, len(devices), rows))
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
def _sizes(config):
    hid, ssm = config["hidden_size"], config["assumed"]
    return {"hid": hid, "inter": config["intermediate_size"],
            "inner": ssm["ssm_expand"] * hid, "states": ssm["ssm_state"],
            "conv": ssm["ssm_conv"], "rank": ssm["dt_rank"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": hid // config["num_attention_heads"]}


def layer_parameters(config):
    """{kind: parameters of one layer of that kind}, from the shapes of
    the equations (biases, norms and the small vectors in)."""
    z = _sizes(config)
    hid, inner, n, r = z["hid"], z["inner"], z["states"], z["rank"]
    kv = z["kv_heads"] * z["head_dim"]
    lam = 4 * z["head_dim"] + 2 * z["head_dim"]
    around = 4 * hid + 3 * hid * z["inter"]     # two LayerNorms, the FFN
    mamba = (hid * 2 * inner + inner * (z["conv"] + 1) + inner * (r + 2 * n)
             + r * inner + inner + inner * n + inner + inner * hid)
    return {"mamba": mamba + around,
            "attn": hid * (hid + 2 * kv) + hid + 2 * kv + hid * hid + hid
            + lam + around,
            "gmu": 2 * hid * inner + around,
            "cross": 2 * (hid * hid + hid) + lam + around}


def kept_kinds(config):
    """[kind] of the kept layers: mamba, attn (window), mamba, attn
    (full), then gmu and cross a kept cross pair."""
    kept = config["kept"]
    return (["mamba", "attn"] * kept["self_pairs"] + ["mamba", "attn"]
            + ["gmu", "cross"] * kept["cross_pairs"])


def band_keys(seq, window):
    """Sum over the positions of the keys each may see: half the square
    for a causal call, the band's area under a window."""
    if not window or window >= seq:
        return seq * (seq + 1) / 2
    return window * (window + 1) / 2 + (seq - window) * window


def flops_per_token(config, job):
    """Forward plus backward FLOPs a token REQUIRES: 6 x every parameter
    outside the table; the head over this chip's rows; differential
    attention's products (a pair's scores once, its 128 values: the four
    kernel calls compute the scores twice, which is not required) over
    the keys the mask leaves; the scans' 9 x channels x states
    operations a token and pass, three passes.  No recomputation."""
    z, seq = _sizes(config), job["seq"]
    per_kind = layer_parameters(config)
    kinds = kept_kinds(config)
    matrices = sum(per_kind[k] for k in kinds) + 2 * z["hid"]
    pairs = z["heads"] // 2
    full = sum(k in ("attn", "cross") for k in kinds) - config["kept"][
        "self_pairs"]
    keys = (full * band_keys(seq, None) + config["kept"]["self_pairs"]
            * band_keys(seq, config["sliding_window"])) / seq
    # a pair and side: QK^T over d, PV over 2d; both sides; x3 for the
    # backward pass
    attention = 3 * 2 * pairs * keys * (2 * z["head_dim"]
                                        + 2 * 2 * z["head_dim"])
    scans = 3 * 9 * z["inner"] * z["states"] * kinds.count("mamba")
    head = 6 * z["hid"] * config["vocab_size"]
    return 6 * matrices + attention + scans + head


def flash_operand(config, job):
    """[B, H, S, D] of the query operand of one flash call of ``job``."""
    z = _sizes(config)
    return (job["batch_per_chip"], z["heads"] // 2, job["seq"],
            z["head_dim"])


def flash_call_cost(kernel, config, job):
    """(FLOPs, bytes) one call of ``kernel`` (a name of the trace, with
    or without ``_band``) needs: the products each kernel performs given
    what it is handed (perf/flops.py FLASH_PRODUCTS) over the keys its
    own mask leaves, and the arrays it must move: query-sized ones of
    heads / 2 heads, key-sized ones of half as many."""
    from perf import flops
    batch, q_heads, seq, dim = flash_operand(config, job)
    base = kernel.replace(BAND, "")
    window = config["sliding_window"] if kernel.endswith(BAND) else None
    work = (flops.FLASH_PRODUCTS[base] * 2 * batch * q_heads * dim
            * band_keys(seq, window))
    query_sized, key_sized = {"flash_fwd": (2, 2), "flash_bwd_dkdv": (2, 4),
                              "flash_bwd_dq": (3, 2)}[base]
    kv_heads = q_heads * config["num_key_value_heads"] // config[
        "num_attention_heads"]
    moved = (query_sized * q_heads + key_sized * kv_heads) * (
        batch * seq * dim * 2)
    return work, moved


def sscan_call_cost(kernel, config, job):
    """(operations, bytes) one call of a scan kernel needs: 9 x channels
    x states operations a position (forward; the backward pass rebuilds
    the states and walks back: three times that), and its reads and
    writes of x, dt, y (and dy, dx, ddt backward) at the float32 the
    kernels take them in, B and C (and dB, dC) unspread."""
    z = _sizes(config)
    tokens = job["batch_per_chip"] * job["seq"]
    wide, narrow = tokens * z["inner"] * 4, tokens * z["states"] * 4
    if kernel == "sscan_fwd":
        return 9 * z["inner"] * z["states"] * tokens, 3 * wide + 2 * narrow
    return 27 * z["inner"] * z["states"] * tokens, 5 * wide + 4 * narrow


# ---------------------------------------------------------------------- #
# parity
# ---------------------------------------------------------------------- #
def reference_params(params, plan):
    """The program's parameter tree (stacked pairs, its own names) under
    the reference's names, one entry of ``layers`` per kept layer in the
    order of ``plan``."""
    def ffn(p):
        return {"W1": p["w1"], "W2": p["w2"]}

    def mixer(p, kind):
        lam = {k: p[k] for k in ("lq1", "lk1", "lq2", "lk2") if k in p}
        if kind.startswith("mamba"):
            return {"Win": p["in_w"], "conv_w": p["conv_w"],
                    "conv_b": p["conv_b"], "Wx": p["x_w"], "Wdt": p["dt_w"],
                    "bdt": p["dt_b"], "A_log": p["A_log"], "Dskip": p["D"],
                    "Wout": p["out_w"]}
        if kind == "gmu":
            return {"Win": p["in_w"], "Wout": p["out_w"]}
        if kind == "cross":
            return {"Wq": p["q_w"], "bq": p["q_b"], "Wo": p["out_w"],
                    "bo": p["out_b"], "g": p["subln_w"], **lam}
        return {"Wqkv": p["qkv_w"], "bqkv": p["qkv_b"], "Wo": p["out_w"],
                "bo": p["out_b"], "g": p["subln_w"], **lam}

    def one(p, kind):
        return {"ln1": p["ln1"], "ln2": p["ln2"], "ffn": ffn(p["ffn"]),
                "mixer": mixer(p["mixer"], kind)}

    def row(tree, i):
        import jax
        return jax.tree.map(lambda a: a[i], tree)

    n_self = sum(kind == "window" for _, kind in plan)
    n_cross = sum(kind == "cross" for _, kind in plan)
    layers = []
    for i in range(n_self):
        pair = row(params["self"], i)
        layers += [one(pair["mamba"], "mamba"), one(pair["attn"], "window")]
    layers += [one(params["mid_mamba"], "mamba_mem"),
               one(params["mid_attn"], "full")]
    for i in range(n_cross):
        pair = row(params["cross"], i)
        layers += [one(pair["gmu"], "gmu"), one(pair["cross"], "cross")]
    return {"embed": params["wte"], "layers": layers, "ln_f": params["ln_f"]}


def reference_plan(model_cfg):
    return tuple((index, KINDS[kind])
                 for index, kind, _ in model_cfg.layer_plan())


def parity(config, job, devices, seed, ids):
    """Engine against reference on ``ids`` [1 row a chip, S]: loss,
    global gradient norm and gradient error of the first step of the
    cell's own model (all six kept layers, the published widths).  The
    engine's 9.8 GB of state and the reference's 5.6 GB of float32
    weights and gradients do not share a chip, so the engine's loss,
    gradients and weights go to the host and the engine is freed before
    the reference runs, layer by layer under ``jax.checkpoint``.
    Returns the numbers and ``ok``."""
    import jax
    import jax.numpy as jnp

    engine = build(config, job, devices, seed,
                   rows_per_chip=ids.shape[0] // len(devices))
    plan = reference_plan(engine.module.config)
    eps, head_dim = config["layer_norm_eps"], engine.module.config.head_dim
    loss = float(engine.forward(*batch_args(ids)))
    # the gradients the grad program handed back for this batch; the
    # engine has no public reader for them
    grads = jax.device_get(reference_params(engine._cached_grads, plan))
    weights = jax.device_get(reference_params(engine.params, plan))
    del engine
    gc.collect()

    first = devices[0]
    ref_loss, ref_grads = jax.jit(
        reference.loss_and_grads, static_argnums=(2, 3, 4, 5))(
        jax.device_put(weights, first), jax.device_put(ids, first), plan,
        eps, head_dim, config["sliding_window"])
    del weights

    @jax.jit
    def compare(ours, ref):
        diff = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, ours, ref)
        return (reference.global_norm(ours), reference.global_norm(ref),
                reference.global_norm(diff))

    norm, ref_norm, err = (float(x) for x in compare(
        jax.device_put(grads, first), ref_grads))
    got = {"loss": loss, "ref_loss": float(ref_loss),
           "grad_norm": norm, "ref_grad_norm": ref_norm}
    got["loss_rel"] = abs(got["loss"] - got["ref_loss"]) / got["ref_loss"]
    got["grad_norm_rel"] = abs(norm - ref_norm) / ref_norm
    got["grad_err_rel"] = err / ref_norm
    got["ok"] = bool(math.isfinite(got["loss"])
                     and got["loss_rel"] <= LOSS_RTOL
                     and got["grad_norm_rel"] <= GRAD_NORM_RTOL
                     and got["grad_err_rel"] <= GRAD_ERR_RTOL)
    return got
