"""The Granite 4.0-H family (Mamba-2 mixers around position-free
grouped-query attention, dense) for the benchmark: how the configuration
file (the released ``config.json`` keys, the kept layers and rows) and a
cell's job become the engine under test, what the family's step and its
kernels require in operations and bytes, and how it is held to the plain
reference in ``granite_hybrid_reference.py``.

From the program this takes the system under test (``GraniteHybridModel``
through ``deepspeed_tpu.initialize``), the tree of its parameters and the
names of its kernels, jitted steps and scopes; nothing of its measurement
code.  The engine plumbing that is no family's own (``ds_config``,
``program_memory``) is the GPT-2 family's.
"""

import gc
import itertools
import math
import time

from perf import flops
from perf.families import gpt2, granite_hybrid_reference as reference

# Names the program gives its kernels and jitted steps; the per-layer
# readers find them in the device trace by these.  The scan's kernels are
# found by their prefix, whatever their number.
FLASH_KERNELS = gpt2.FLASH_KERNELS
SSD_KERNEL_PREFIX = "ssd_"
GRAD_PROGRAM = gpt2.GRAD_PROGRAM
APPLY_PROGRAM = gpt2.APPLY_PROGRAM
ds_config = gpt2.ds_config
program_memory = gpt2.program_memory
# the parts of scope ``ssm`` around the scan: the work of the vector unit
SSM_AROUND_PARTS = ("conv", "gate")

MAMBA, ATTENTION = reference.MAMBA, reference.ATTENTION

# Parity of the engine (bf16 compute, fp32 master weights, bf16 gradient
# buffers; the scan's running sums, exponentials and states in float32,
# its products on bf16 operands: ops/ssd_scan.py) with the float32
# reference on the cell's own row of 4,096 tokens, the cell's own engine
# (all ten kept layers at the published widths, the byte budget's real
# plan).  Every number is relative to the reference's:
#   loss       the mean cross-entropy over 4,095 tokens; rounding hardly
#              moves it, it guards the terms (a dropped multiplier, a
#              scale of 1/8 for 1/64, a rotation: each moves it by 1e-2
#              or more, tests/unit/test_granite_hybrid.py).
#   grad_norm, grad_err   the global norm's and every entry's, as for
#              GPT-2 (perf/families/gpt2.py has what each catches).
#   a_log, dt_bias, d_skip, conv, gate_norm   the gradient error of the
#              scan's own leaves over the nine mixers, each against the
#              reference's norm of the same leaves: 64 numbers a layer
#              (A_log, dt_bias, D), 21,760 (the conv's taps and bias) and
#              4,096 (the gated norm's gain), which beside 772M would
#              hide in a norm, and the first two exist only through the
#              decay.
# Each limit lies between two readings on the v5e at the cell's size (my
# chip runs, PR 53; PERF.md section 6 has them): the engine's worst over
# its seeds, and the reference itself with its recurrence carrying an
# fp8 STATE (every position's state scaled to e4m3's range and rounded
# before the next: the nearest precision under the bf16 the job states
# that a state can be carried in), put through ``judge`` as if it were
# the program, against itself in float32 on the same row.  A bf16 state
# reads UNDER the engine (grad_err 3.8e-3, a_log 1.3e-2): the engine's
# distance from the reference is the bf16 of everything around the scan,
# so bf16 inside it cannot be told from the engine and is no control.
#                 engine, worst of 11   fp8 state
#   loss            9.2e-6                0 (the control's forward is exact)
#   grad_norm       6.5e-4                1.0e-2
#   grad_err        3.0e-2                0.13
#   a_log           3.9e-2                0.86
#   dt_bias         3.9e-2                0.75
#   d_skip          3.3e-2                0.11
#   conv            2.8e-2                0.16
#   gate_norm       3.0e-2                0.11
# The loss does not tell the two apart (a mean over 4,095 tokens), so its
# limit guards terms alone; the other seven stand near the geometric
# middle of their two readings, no closer than 1.8 times to either, and
# each of them refuses the fp8 state.
LOSS_RTOL = 1.5e-4
GRAD_NORM_RTOL = 3e-3
GRAD_ERR_RTOL = 7e-2
LEAF_RTOL = {"a_log": 1.2e-1, "dt_bias": 1.2e-1, "d_skip": 6e-2, "conv": 6e-2,
             "gate_norm": 5.5e-2}
LEAVES = {"a_log": ("A_log",), "dt_bias": ("dt_bias",), "d_skip": ("D",),
          "conv": ("conv_w", "conv_b"), "gate_norm": ("norm_w",)}


def model_config(config, job):
    from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig
    if (config["hidden_act"] != "silu" or not config["tie_word_embeddings"]
            or config["position_embedding_type"] != "nope"
            or config["num_local_experts"] or config["attention_bias"]
            or config["mamba_proj_bias"] or not config["mamba_conv_bias"]
            or config["normalization_function"] != "rmsnorm"
            or config["shared_intermediate_size"]
            != config["intermediate_size"]):
        raise ValueError(
            "the granite_hybrid family computes a dense silu-gated FFN, a "
            "tied head, RMSNorm, attention without positions or bias and "
            "Mamba-2 mixers whose conv alone has a bias")
    return GraniteHybridConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        shared_intermediate_size=config["shared_intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_expand=config["mamba_expand"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_chunk_size=config["mamba_chunk_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        rms_norm_eps=config["rms_norm_eps"],
        initializer_range=config["assumed"]["initializer_range"],
        bf16=True,
        activation_checkpointing=bool(job["activation_checkpointing"]))


def build(config, job, devices, seed, rows_per_chip=None):
    """The engine of ``job`` on ``devices`` (a ``data`` mesh over all of
    them), weights made on the device from ``seed`` in one jitted call."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.granite_hybrid import GraniteHybridModel

    model = GraniteHybridModel(model_config(config, job))
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
    heads, dim = config["mamba_n_heads"], config["mamba_d_head"]
    return {"hid": config["hidden_size"],
            "inter": config["shared_intermediate_size"],
            "inner": heads * dim, "ssm_heads": heads, "ssm_dim": dim,
            "states": config["mamba_n_groups"] * config["mamba_d_state"],
            "conv": config["mamba_d_conv"],
            "chunk": config["mamba_chunk_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"] // config[
                "num_attention_heads"]}


def layer_parameters(config):
    """{kind: parameters of one layer of that kind}, from the shapes of
    the equations: the mixer, the gated FFN and two norm gains."""
    z = _sizes(config)
    hid, inner, n = z["hid"], z["inner"], z["states"]
    conv_dim = inner + 2 * n
    around = 2 * hid + 3 * hid * z["inter"]
    mamba = (hid * (inner + conv_dim + z["ssm_heads"])      # in_proj
             + conv_dim * (z["conv"] + 1)                   # taps, bias
             + 3 * z["ssm_heads"]                           # dt_bias, A, D
             + inner + inner * hid)                         # norm, out_proj
    kv = z["kv_heads"] * z["head_dim"]
    return {MAMBA: mamba + around,
            ATTENTION: 2 * hid * hid + 2 * hid * kv + around}


def kept_kinds(config):
    return list(config["layer_types"][:config["num_hidden_layers"]])


def parameters(config):
    """Parameters of the cut: the kept layers, this chip's rows of the
    tied table, the final norm."""
    per_kind = layer_parameters(config)
    return (sum(per_kind[k] for k in kept_kinds(config))
            + config["vocab_size"] * config["hidden_size"]
            + config["hidden_size"])


def scan_flops_per_token(config):
    """Operations a token, mixer and FORWARD pass of the chunked scan at
    the configuration's own chunk Q: the scores C B^T once for all heads
    (2 Q N), the masked scores on the values (2 Q H P), the chunk's state
    and the state's share of the output (2 N H P each)."""
    z = _sizes(config)
    q, n, hp = z["chunk"], z["states"], z["inner"]
    return 2 * q * n + 2 * q * hp + 4 * n * hp


def flops_per_token(config, job):
    """Forward plus backward FLOPs a token REQUIRES: 6 x every parameter
    outside the table; the head over this chip's rows; the attention
    layers' scores and values over half the square; the scans' products,
    three passes' worth (each forward product has two transposes).  No
    recomputation."""
    z, kinds = _sizes(config), kept_kinds(config)
    per_kind = layer_parameters(config)
    matrices = sum(per_kind[k] for k in kinds) + z["hid"]
    attention = kinds.count(ATTENTION) * 3 * 2 * 2 * (
        (job["seq"] + 1) / 2) * z["heads"] * z["head_dim"]
    scans = kinds.count(MAMBA) * 3 * scan_flops_per_token(config)
    head = 6 * z["hid"] * config["vocab_size"]
    return 6 * matrices + attention + scans + head


def flash_operand(config, job):
    """[B, H, S, D] of the query operand of one chip's flash call."""
    z = _sizes(config)
    return (job["batch_per_chip"], z["heads"], job["seq"], z["head_dim"])


def flash_call_cost(kernel, config, job):
    """(FLOPs, bytes) one call of an attention kernel needs, counted by
    the mathematics whatever kernel implements it: 32 query heads of 64,
    causal at half the square; the arrays of 8 key/value heads are a
    quarter of a query-sized one."""
    batch, heads, seq, dim = flash_operand(config, job)
    z = _sizes(config)
    query_sized, key_sized = {"flash_fwd": (2, 2), "flash_bwd_dkdv": (4, 4),
                              "flash_bwd_dq": (3, 2)}[kernel]
    moved = (query_sized * heads + key_sized * z["kv_heads"]) * (
        batch * seq * dim * 2)
    return flops.flash_call_flops(kernel, batch, heads, seq, dim), moved


def ssd_call_cost(kernel, config, job):
    """(operations, bytes) one call of a scan kernel needs, by the
    MATHEMATICS at the configuration's own chunk, whatever implements it.
    Forward: ``scan_flops_per_token``.  Backward: every forward product's
    two transposes, twice that (the kernel's recomputation of C B^T and
    its running sums of da are not required and not counted).  Bytes:
    x, y, dt, B and C once each (backward: x, dy, dx, dt, da, B, C, dB,
    dC), and the chunk-entry states written (backward: read, and their
    cotangents written), at the dtypes the calls are handed.  A name this
    family does not know is credited nothing."""
    z = _sizes(config)
    tokens = job["batch_per_chip"] * job["seq"]
    wide = tokens * z["inner"] * 2                  # bf16 [S, H P]
    per_head = tokens * z["ssm_heads"] * 4          # float32 [S, H]
    narrow = tokens * z["states"]                   # B or C, a byte each
    entries = -(-tokens // z["chunk"]) * z["inner"] * z["states"] * 4
    work = scan_flops_per_token(config) * tokens
    if kernel == "ssd_fwd":
        return work, 2 * wide + per_head + 2 * 2 * narrow + entries
    if kernel == "ssd_bwd":
        return 2 * work, (3 * wide + 2 * per_head + 2 * 2 * narrow
                          + 2 * 4 * narrow + 2 * entries)
    return 0, 0


# ---------------------------------------------------------------------- #
# parity
# ---------------------------------------------------------------------- #
def reference_spec(config):
    """The reference with each run of like layers rolled (one traced
    layer a run) at the published depth; the plain Python loops where
    they compile fast."""
    layers = config["num_hidden_layers"]
    z = _sizes(config)
    return reference.Spec(
        kinds=tuple(kind for kind, _ in itertools.groupby(
            kept_kinds(config))),
        heads=z["heads"], kv_heads=z["kv_heads"], head_dim=z["head_dim"],
        ssm_heads=z["ssm_heads"], ssm_dim=z["ssm_dim"], states=z["states"],
        eps=config["rms_norm_eps"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        rolled=layers > 4)


def reference_params(params, spec):
    """The program's parameter tree (stacked runs, its own names, the
    fused q/k/v matrix, the input projection in two leaves) under the
    reference's names: one dict of stacked arrays a run."""
    import jax.numpy as jnp

    def run(kind, p):
        out = {"norm1": p["ln1"], "norm2": p["ln2"],
               "Wffn_in": p["ffn"]["w1"], "Wffn_out": p["ffn"]["w2"]}
        m = p["mixer"]
        if kind == MAMBA:
            # the published in_proj: z, xBC, dt
            out.update(
                Win=jnp.concatenate([m["in_w"], m["dt_w"]], axis=-1),
                conv_w=m["conv_w"], conv_b=m["conv_b"],
                dt_bias=m["dt_bias"], A_log=m["A_log"], D=m["D"],
                norm_w=m["norm_w"], Wout=m["out_w"])
        else:
            width = spec.heads * spec.head_dim
            kv = spec.kv_heads * spec.head_dim
            q, k, v = jnp.split(m["qkv_w"], [width, width + kv], axis=-1)
            out.update(Wq=q, Wk=k, Wv=v, Wo=m["out_w"])
        return out

    return {"embed": params["wte"], "norm": params["ln_f"],
            "layers": [run(kind, p)
                       for kind, p in zip(spec.kinds, params["runs"])]}


def scan_leaves(tree, spec, names):
    """The leaves ``names`` of every Mamba run of a reference tree."""
    return [run[name] for kind, run in zip(spec.kinds, tree["layers"])
            if kind == MAMBA for name in names]


def program_side(config, job, devices, seed, ids):
    """What the program gives on ``ids`` (the cell's batch, so the byte
    budget plans what it plans for the window): the loss of one grad
    program, the gradients it handed back and its weights, under the
    reference's names.  All on the host, the engine freed."""
    import jax

    began = time.perf_counter()
    engine = build(config, job, devices, seed,
                   rows_per_chip=ids.shape[0] // len(devices))
    spec = reference_spec(config)
    out = {"loss": float(engine.forward(*batch_args(ids)))}
    # the gradients the grad program handed back for this batch; the
    # engine has no public reader for them
    out["grads"] = jax.device_get(
        reference_params(engine._cached_grads, spec))
    engine._cached_grads = None
    out["weights"] = jax.device_get(reference_params(engine.params, spec))
    if engine.monitor is not None:
        # its writer thread holds the engine, and so its state
        engine.monitor.close()
    del engine
    gc.collect()
    out["program_s"] = time.perf_counter() - began
    return out


def judge(config, program, ids, device):
    """The comparison of ``program_side``'s result with the reference on
    ``device``; the numbers, which of them ``failed`` and ``ok``."""
    import jax
    import jax.numpy as jnp

    began = time.perf_counter()
    spec = reference_spec(config)
    # traced anew each call: the reference's small functions are looked
    # up as they stand (a test replaces one to see the comparison fail)
    ref_loss, ref_grads = jax.jit(
        lambda w, i: reference.loss_and_grads(w, i, spec))(
        jax.device_put(program["weights"], device),
        jax.device_put(ids, device))

    @jax.jit
    def compare(ours, theirs):
        def apart(a, b):
            return reference.global_norm(jax.tree.map(
                lambda x, y: x.astype(jnp.float32) - y, a, b))
        by_leaf = {
            name: (apart(scan_leaves(ours, spec, leaves),
                         scan_leaves(theirs, spec, leaves)),
                   reference.global_norm(scan_leaves(theirs, spec, leaves)))
            for name, leaves in LEAVES.items()}
        return (reference.global_norm(ours), reference.global_norm(theirs),
                apart(ours, theirs), by_leaf)

    norm, ref_norm, err, by_leaf = jax.device_get(compare(
        jax.device_put(program["grads"], device), ref_grads))
    norm, ref_norm, err, ref_loss = (float(v) for v in (
        norm, ref_norm, err, ref_loss))
    got = {"loss": program["loss"], "ref_loss": ref_loss,
           "grad_norm": norm, "ref_grad_norm": ref_norm,
           "loss_rel": abs(program["loss"] - ref_loss) / ref_loss,
           "grad_norm_rel": abs(norm - ref_norm) / ref_norm,
           "grad_err_rel": err / ref_norm}
    limits = {"loss_rel": LOSS_RTOL, "grad_norm_rel": GRAD_NORM_RTOL,
              "grad_err_rel": GRAD_ERR_RTOL}
    for name, (apart, size) in by_leaf.items():
        got[name + "_ref_norm"] = float(size)
        got[name + "_err_rel"] = float(apart) / float(size)
        limits[name + "_err_rel"] = LEAF_RTOL[name]
    got["failed"] = [name for name, limit in limits.items()
                     if not got[name] <= limit]
    got["ok"] = bool(math.isfinite(got["loss"]) and not got["failed"])
    got["seconds"] = {"program": round(program.get("program_s", 0.0), 1),
                      "reference": round(time.perf_counter() - began, 1)}
    return got


def parity(config, job, devices, seed, ids):
    """Engine against reference on ``ids`` (the cell's batch, [rows, S]),
    all the kept layers at the published widths (see the limits above).
    The engine's 12.4 GB of state and the reference's float32 weights and
    gradients do not share a chip: the engine's results go to the host
    and the engine is freed before the reference runs, layer by layer
    under ``jax.checkpoint``, the recurrence position by position in
    blocks of 64, the logits by blocks of rows.  Returns the numbers and
    ``ok``."""
    return judge(config, program_side(config, job, devices, seed, ids), ids,
                 devices[0])
