"""The Ouro (LoopLM) family for the benchmark: how the configuration file
(the released ``config.json`` keys and the kept layers) and a cell's job
become the engine under test, what the family's step and its kernels
require in operations and bytes, and how it is held to the plain
reference in ``ouro_reference.py``.

From the program this takes the system under test (``OuroModel`` through
``deepspeed_tpu.initialize``), the tree of its parameters, the names of
its kernels, jitted steps and scopes, and the counters its engine
accumulates; nothing of its measurement code.  The engine plumbing that
is no family's own is the GPT-2 family's.
"""

import gc
import math
import time

from perf import flops
from perf.families import gpt2, ouro_reference as reference

# Names the program gives its kernels, jitted steps and scopes; the
# per-layer readers find them in the device trace by these.
FLASH_KERNELS = gpt2.FLASH_KERNELS
GRAD_PROGRAM = gpt2.GRAD_PROGRAM
APPLY_PROGRAM = gpt2.APPLY_PROGRAM
ds_config = gpt2.ds_config
# the scope that holds all of the exit work: the final norm after every
# pass, the gate, the passes over the head, the distribution, the KL term
EXIT_REGION = "exit"

# Parity of the engine (bf16 compute, fp32 master weights, bf16 gradient
# buffers, the gate's product, sigmoid and exit distribution in float32)
# with the float32 reference on the cell's own row of 4,096 tokens, the
# cell's own engine (the 8 kept layers run 4 times at the published
# widths, the byte budget's real plan).  The gate is not left at the
# zeros it starts from, where every token's exits are (1/2, 1/4, 1/8,
# 1/8) in any precision and its gradient alone would be compared: the
# judged engine gets gate weights from the seed (``seeded_gate``: normal
# of the matrices' 0.02 and a bias of 0.1, so the logits differ by token
# with a spread of about one), as ISSUE 45's tests do.
#   loss, task_loss, exit_kl   the objective the engine reports and its
#              two counters, the first two relative to the reference's,
#              the KL term (a few tenths of a nat, 0 where the exits are
#              uniform) by its absolute error.  Means over 4,095 tokens,
#              which rounding hardly moves; they guard the terms
#              themselves (a dropped KL term, a wrong beta, a missing
#              pass, no norm between passes: each moves one by 2e-3 or
#              more, tests/perf/test_ouro_reference.py).
#   exit_mass_err   the largest |mean p_t - reference's| over the exits.
#   gate_err   the model's gate (``OuroModel.exit_probabilities``, a
#              program of its own) against the reference's gate and
#              distribution in float32, both ON WHAT THE PROGRAM'S STACK
#              PRODUCED (its own bf16 h_t of the cell's row, the gate's
#              weights as the engine rounds them), token by token: the
#              gate's arithmetic alone, apart from the bf16 of everything
#              before it, which moves p by as much as a bf16 gate would.
#              Not the p of the forward program itself: there XLA feeds
#              the gate the final norm's float32 value before it is
#              rounded to the bf16 h_t the program hands out (excess
#              precision), and p then differs from any recomputation on
#              the rounded h_t by 4e-4 to 1.1e-3, ten seeds, which is
#              rounding of h and no fault of the gate.  exit_losses_rel:
#              the four passes' own mean cross-entropies from the same
#              forward pass, the worst of them.
#   grad_norm, grad_err   the global norm's and every entry's, as for
#              GPT-2 (perf/families/gpt2.py has what each catches).
#   gate_grad_err   the gate's 2,049 numbers apart: beside 612M they
#              would hide in a norm, and they are the only gradient that
#              exists only through the exit distribution.
#   objective_rel   the reported loss against task + beta x kl of the
#              counters: that the counters are the objective's terms.
# Each limit lies between two readings on the v5e (my chip runs, PR 45;
# PERF.md section 6 has the runs): the engine's worst over its seeds, and
# the reference itself with every product's operands, forward and
# backward, in fp8 (e4m3, each tensor scaled to the format's range), the
# precision below the engine's, against itself in float32 on the same
# row.  The reference with bf16 products is the engine's own precision
# and lies inside every limit, as it should.
#                      engine, worst   bf16 products   fp8 scaled a tensor
#   loss / task_loss_rel  2.2e-4 / 2.3e-4  6.2e-5 / 3.9e-5  9.0e-4 / 6.6e-4
#   exit_losses_rel       5.7e-4           2.1e-4           4.4e-3
#   exit_kl_err           4.7e-3           2.7e-3           2.8e-2
#   exit_mass_err         4.9e-3           1.9e-3           1.6e-2
#   grad_norm_rel         1.3e-2           2.3e-3           1.8e-3
#   grad_err_rel          4.5e-2           2.5e-2           0.31
#   gate_grad_err_rel     6.8e-2           1.2e-2           0.29
#   gate_err_rel          1.2e-6           (a bf16 gate: 2.7e-3)
# (25 runs of the engine on 25 seeds, gate_err_rel the last ten of them;
# seed 2147485001 for the reference's two.)  The engine's worst scatters
# by seed (gate_grad_err_rel reads 0.9 to 3% on twenty seeds and 3.7 to
# 6.8% on five: the seeded gate's 2,049 gradients are sums of 4,095 terms
# that nearly cancel), so the limits stand at about twice the worst of
# twenty-five and still under the fp8 reading.  The gradient's norm does NOT tell the precisions apart
# (unbiased rounding leaves a norm alone; the engine's reads LOW on every
# seed, the bias of its bf16 gradient buffers that GPT-2's cells show
# too): its limit guards a dropped term (the reference without its KL
# term reads 7.8e-2), and fp8 is refused by six other numbers.  Two
# precisions of a PART the issue asked about: a bf16 gate (logit, sigmoid
# and distribution in bf16) moves no end-to-end number by more than the
# engine's own bf16 does (grad_err 2.3e-3, gate_grad_err 4.5e-3,
# exit_kl_err 6.7e-4) and is refused by gate_err_rel alone, which reads
# 2.7e-3 for it; bf16 SCORES inside attention (the scaled scores rounded
# before the softmax) read grad_err 1.2e-3, gate_grad_err 3.5e-4, loss
# 2.0e-6: a thirtieth of what the engine's bf16 products already cost, so
# no comparison of a bf16 engine with a float32 reference can refuse
# them, and none here claims to; the flash kernels' own parity
# (tests/tpu) holds their scores.
LOSS_RTOL = 5e-4
EXIT_LOSS_RTOL = 1.6e-3
KL_ATOL = 1.2e-2
MASS_ATOL = 1e-2
GRAD_NORM_RTOL = 4e-2
GRAD_ERR_RTOL = 0.12
GATE_GRAD_ERR_RTOL = 0.2
GATE_RTOL = 5e-5


def model_config(config, job):
    from deepspeed_tpu.models.ouro import OuroConfig
    layers = config["num_hidden_layers"]
    if (config["tie_word_embeddings"] or config["hidden_act"] != "silu"
            or config["rope_scaling"] is not None
            or config["use_sliding_window"]
            or set(config["layer_types"][:layers]) != {"full_attention"}
            or config["num_attention_heads"] * config["head_dim"]
            != config["hidden_size"]):
        raise ValueError("the ouro family computes an untied head, silu, "
                         "unscaled rotary, full attention in every layer "
                         "and heads that make up the hidden size only")
    assumed = config["assumed"]
    return OuroConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=layers,
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        total_ut_steps=config["total_ut_steps"],
        exit_kl_weight=assumed["exit_kl_weight"],
        initializer_range=assumed["initializer_range"],
        bf16=True,
        activation_checkpointing=bool(job["activation_checkpointing"]))


def build(config, job, devices, seed, rows_per_chip=None):
    """The engine of ``job`` on ``devices`` (a ``data`` mesh over all of
    them), weights made on the device from ``seed`` in one jitted call."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.ouro import OuroModel

    model = OuroModel(model_config(config, job))
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
    """Rows of the vocabulary traffic may draw: the published ones."""
    return config["vocab_size"]


def program_memory(engine, ids):
    """The GPT-2 family's account of the two step programs, and beside it
    the exit gate's counters as means over the steps since the last read
    (the window's, where nothing read them before)."""
    out = gpt2.program_memory(engine, ids)
    counters = engine.model_counters()
    if counters:
        out["exit"] = counters
    return out


# ---------------------------------------------------------------------- #
# what the step and its kernels require
# ---------------------------------------------------------------------- #
def layer_matrices(config):
    """Parameters of a layer's matrices: q, k, v, o and the gated FFN."""
    hid = config["hidden_size"]
    width = config["num_attention_heads"] * config["head_dim"]
    return 4 * hid * width + 3 * hid * config["intermediate_size"]


def flops_per_token(config, job):
    """Forward plus backward FLOPs a token REQUIRES: 6 x every matrix
    entry it multiplies, a layer's ``total_ut_steps`` times (the same
    weights, used that often) and the head's as often (every pass is
    scored); the scores and values of each of the layers x passes
    attention calls over half the square; the gate's vector after every
    pass but the last.  No recomputation."""
    passes, layers = config["total_ut_steps"], config["num_hidden_layers"]
    hid = config["hidden_size"]
    matrices = passes * (layers * layer_matrices(config)
                         + hid * config["vocab_size"]) + (passes - 1) * hid
    # QK^T and PV: 2 products x 2 FLOPs x keys x heads x d; x3 in all
    attention = passes * layers * 3 * 2 * 2 * ((job["seq"] + 1) / 2) * (
        config["num_attention_heads"] * config["head_dim"])
    return 6 * matrices + attention


def flash_operand(config, job):
    """[B, H, S, D] of one chip's flash-attention call in ``job``."""
    return (job["batch_per_chip"], config["num_attention_heads"],
            job["seq"], config["head_dim"])


def flash_call_cost(kernel, config, job):
    """(FLOPs, bytes) one call of an attention kernel needs, counted by
    the mathematics whatever kernel implements it: 16 heads of 128 on as
    many key/value heads, causal at half the square."""
    operand = flash_operand(config, job)
    return (flops.flash_call_flops(kernel, *operand),
            flops.flash_call_bytes(kernel, *operand))


# ---------------------------------------------------------------------- #
# parity
# ---------------------------------------------------------------------- #
def reference_spec(config):
    """The reference rolled (two scans of one traced layer) at the
    published depth; the plain Python loops where they compile fast."""
    return reference.Spec(
        rolled=config["total_ut_steps"] * config["num_hidden_layers"] > 8,
        heads=config["num_attention_heads"], head_dim=config["head_dim"],
        theta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        passes=config["total_ut_steps"],
        beta=config["assumed"]["exit_kl_weight"])


def reference_params(params, stacked=False):
    """The program's parameter tree (one stacked group, its own names,
    fused q/k/v and gate/up matrices) under the reference's names, one
    entry of ``layers`` per kept layer, or with ``stacked`` one dict of
    the layers' arrays as the program stacks them (what the rolled
    reference scans)."""
    import jax
    import jax.numpy as jnp

    def one(p):
        q, k, v = jnp.split(p["attn"]["qkv_w"], 3, axis=-1)
        gate, up = jnp.split(p["ffn"]["w1"], 2, axis=-1)
        return {"norm1": p["ln1"], "norm2": p["ln2"], "norm3": p["ln3"],
                "norm4": p["ln4"], "Wq": q, "Wk": k, "Wv": v,
                "Wo": p["attn"]["out_w"], "Wgate": gate, "Wup": up,
                "Wdown": p["ffn"]["w2"]}

    group = params["layers"]
    return {"embed": params["wte"], "head": params["head"],
            "norm": params["ln_f"], "gate_w": params["gate"]["w"],
            "gate_b": params["gate"]["b"],
            "layers": one(group) if stacked else [
                one(jax.tree.map(lambda a, i=i: a[i], group))
                for i in range(jax.tree.leaves(group)[0].shape[0])]}


def gate_of(weights):
    """The gate's 2,049 numbers of a ``reference_params`` tree."""
    return {"w": weights["gate_w"], "b": weights["gate_b"]}


def seeded_gate(params, seed, std):
    """``params`` with the gate's weights normal(0, std) and its bias
    normal(0, 0.1) from ``seed``."""
    import jax

    k_w, k_b = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(seed % (2 ** 31)), 45))
    gate = params["gate"]
    return {**params, "gate": {
        "w": jax.device_put(std * jax.random.normal(
            k_w, gate["w"].shape, gate["w"].dtype), gate["w"].sharding),
        "b": jax.device_put(0.1 * jax.random.normal(
            k_b, gate["b"].shape, gate["b"].dtype), gate["b"].sharding)}}


def program_side(config, job, devices, seed, ids):
    """What the program gives on ``ids`` (the cell's batch, so the byte
    budget plans what it plans for the window): the loss of one grad
    program, its counters, the gradients it handed back and its weights.
    All on the host, the engine freed."""
    import jax

    began = time.perf_counter()
    engine = build(config, job, devices, seed,
                   rows_per_chip=ids.shape[0] // len(devices))
    engine.params = seeded_gate(engine.params, seed,
                                config["assumed"]["initializer_range"])
    model = engine.module

    @jax.jit
    def forward(params, ids):
        # the compute-dtype copy of the weights the grad program makes
        cast = jax.tree.map(lambda a: a.astype(model.config.dtype), params)
        losses, _, valid, read = model.exit_terms(cast, ids,
                                                  with_inputs=True)
        return (cast["gate"], read[:-1], valid,
                jax.numpy.sum(losses * valid, axis=1) / valid.sum())

    gate, read, valid, exit_losses = forward(engine.params, ids)
    out = {"exit_p": jax.device_get(
               jax.jit(model.exit_probabilities)(gate, read)),
           "gate_read": jax.device_get(read),
           "valid": jax.device_get(valid),
           "exit_losses": [float(v) for v in exit_losses]}
    del read
    out["loss"] = float(engine.forward(*batch_args(ids)))
    out["counters"] = engine.model_counters()
    # the gradients the grad program handed back for this batch; the
    # engine has no public reader for them
    stacked = reference_spec(config).rolled
    out["grads"] = jax.device_get(
        reference_params(engine._cached_grads, stacked))
    engine._cached_grads = None
    out["weights"] = jax.device_get(reference_params(engine.params, stacked))
    if engine.monitor is not None:
        # its writer thread holds the engine, and so its state
        engine.monitor.close()
    del engine, model
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
    (ref_loss, ref), ref_grads = jax.jit(
        lambda w, i: reference.loss_and_grads(w, i, spec))(
        jax.device_put(program["weights"], device),
        jax.device_put(ids, device))

    @jax.jit
    def compare(ours, theirs):
        def apart(a, b):
            return reference.global_norm(jax.tree.map(
                lambda x, y: x.astype(jnp.float32) - y, a, b))
        return (reference.global_norm(ours), reference.global_norm(theirs),
                apart(ours, theirs),
                reference.global_norm(gate_of(theirs)),
                apart(gate_of(ours), gate_of(theirs)))

    norm, ref_norm, err, ref_gate_norm, gate_grad_err = (
        float(x) for x in compare(
            jax.device_put(program["grads"], device), ref_grads))

    @jax.jit
    def own_exits(read, w, b):
        """The reference's gate and distribution on what the program's
        gate read."""
        with jax.default_matmul_precision("highest"):
            return reference.exit_distribution(jnp.stack([
                reference.exit_gate(h.astype(jnp.float32), w, b)
                for h in read]))

    # the gate's weights as the program read them: in its compute dtype
    read = jax.device_put(program["gate_read"], device)
    gate = {name: jnp.asarray(value).astype(read.dtype).astype(jnp.float32)
            for name, value in gate_of(program["weights"]).items()}
    keep = program["valid"] > 0
    ours_p = program["exit_p"][:, keep]
    own_p = jax.device_get(own_exits(read, gate["w"], gate["b"]))[:, keep]
    counters = program["counters"]
    ref = {name: jax.device_get(value) for name, value in ref.items()}
    ref_loss, ref_task, ref_kl = (float(ref_loss), float(ref["task_loss"]),
                                  float(ref["exit_kl"]))
    masses = [counters[f"exit_mass_{t + 1}"] for t in range(spec.passes)]
    got = {"loss": program["loss"], "ref_loss": ref_loss,
           "task_loss": counters["task_loss"], "ref_task_loss": ref_task,
           "exit_kl": counters["exit_kl"], "ref_exit_kl": ref_kl,
           "exit_mass": masses,
           "ref_exit_mass": [float(m) for m in ref["exit_mass"]],
           "ref_exit_losses": [float(v) for v in ref["exit_losses"]],
           "exit_step_mean": counters["exit_step_mean"],
           "grad_norm": norm, "ref_grad_norm": ref_norm,
           "ref_gate_grad_norm": ref_gate_norm,
           "loss_rel": abs(program["loss"] - ref_loss) / ref_loss,
           "task_loss_rel": abs(counters["task_loss"] - ref_task) / ref_task,
           "exit_kl_err": abs(counters["exit_kl"] - ref_kl),
           "exit_mass_err": max(abs(a - float(b)) for a, b in zip(
               masses, ref["exit_mass"])),
           "grad_norm_rel": abs(norm - ref_norm) / ref_norm,
           "grad_err_rel": err / ref_norm,
           "gate_grad_err_rel": gate_grad_err / ref_gate_norm,
           "gate_err_rel": float(
               ((ours_p - own_p) ** 2).mean() ** 0.5
               / (own_p ** 2).mean() ** 0.5),
           "exit_losses": program["exit_losses"],
           "exit_losses_rel": max(
               abs(a - float(b)) / float(b) for a, b in zip(
                   program["exit_losses"], ref["exit_losses"])),
           # the objective the engine reports is its two counters' sum
           "objective_rel": abs(program["loss"] - (
               counters["task_loss"] + spec.beta * counters["exit_kl"]))
           / program["loss"]}
    limits = {"loss_rel": LOSS_RTOL, "task_loss_rel": LOSS_RTOL,
              "exit_kl_err": KL_ATOL, "exit_mass_err": MASS_ATOL,
              "grad_norm_rel": GRAD_NORM_RTOL, "grad_err_rel": GRAD_ERR_RTOL,
              "gate_grad_err_rel": GATE_GRAD_ERR_RTOL,
              "gate_err_rel": GATE_RTOL, "exit_losses_rel": EXIT_LOSS_RTOL,
              "objective_rel": LOSS_RTOL}
    got["failed"] = [name for name, limit in limits.items()
                     if not got[name] <= limit]
    got["ok"] = bool(math.isfinite(got["loss"]) and not got["failed"])
    got["seconds"] = {"program": round(program.get("program_s", 0.0), 1),
                      "reference": round(time.perf_counter() - began, 1)}
    return got


def parity(config, job, devices, seed, ids):
    """Engine against reference on ``ids`` (the cell's batch, [rows, S]),
    the kept layers run ``total_ut_steps`` times at the published widths
    (see the limits above).  The engine's 8.6 GB of state and the
    reference's float32 weights and gradients do not share a chip: the
    engine's results go to the host and the engine is freed before the
    reference runs, layer application by layer application under
    ``jax.checkpoint``, the logits by blocks of rows.  Returns the
    numbers and ``ok``."""
    return judge(config, program_side(config, job, devices, seed, ids), ids,
                 devices[0])
