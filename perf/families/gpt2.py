"""The GPT-2 family for the benchmark: how a configuration file (the
released ``config.json`` keys) and a cell's job become the engine under
test, what the family's step requires in operations, and how it is held
to the plain reference in ``gpt2_reference.py``.

From the program this takes the system under test (``GPT2Model`` through
``deepspeed_tpu.initialize``), the tree of its parameters and the names
of its kernels and jitted steps; nothing of its measurement code.
"""

import math

import numpy as np

from perf import flops
from perf.families import gpt2_reference

# Names the program gives its kernels and jitted steps; the per-layer
# readers find them in the device trace by these.
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")
GRAD_PROGRAM = "loss_and_grads"
APPLY_PROGRAM = "apply_step"

# Parity of the engine (bf16 compute, fp32 master weights, bf16 gradient
# buffers, the cell's own layout) with the float32 reference on one
# seeded batch of 4,096 tokens at the published widths, two layers,
# dropout off.  Three numbers, each relative to the reference's:
#   loss       bf16 rounds every activation to 8 bits of mantissa (2e-3 a
#              value), but the mean over 4,096 tokens moves far less.
#   grad_norm  the global L2 norm.  Unbiased rounding hardly moves a
#              norm; what it catches is a bias or a dropped term.
#   grad_err   |g_engine - g_reference| / |g_reference| over all entries:
#              the rounding itself, entry by entry.
# Measured on the v5e (my chip runs, PR 22; 22 seeds over the three
# cells): loss within 3.3e-5; gradient norm 0.3 to 0.4% LOW at S=128 and
# 0.9 to 1.2% LOW at S=1,024, on one chip and on four, always low (a
# bias of the bf16 backward pass, PERF.md section 7); gradient error
# 2.3 to 5.2%.  The bounds are about four times the loss's and twice the
# gradients' worst reading.  An fp8 product (3 bits of mantissa, 6% a
# value) multiplies the gradient error several times over; a dropped term
# (the causal mask, the 1/sqrt(d), the tied head's share of d/d wte, a
# wrong GELU) moves all three by far more (tests/perf shows it for one).
LOSS_RTOL = 1.5e-4
GRAD_NORM_RTOL = 2.5e-2
GRAD_ERR_RTOL = 0.1


def model_config(config, job, layers=None, dropout=True):
    from deepspeed_tpu.models import GPT2Config
    if config["activation_function"] != "gelu_new":
        raise ValueError("the gpt2 family computes gelu_new only, got "
                         f"{config['activation_function']!r}")
    embd, attn, resid = (config[k] if dropout else 0.0 for k in (
        "embd_pdrop", "attn_pdrop", "resid_pdrop"))
    return GPT2Config(
        vocab_size=config["assumed"]["vocab_rows_padded"],
        n_positions=config["n_positions"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"] if layers is None else layers,
        num_heads=config["n_head"], intermediate_size=config.get("n_inner"),
        embd_dropout=embd, attn_dropout=attn, hidden_dropout=resid,
        layer_norm_eps=config["layer_norm_epsilon"],
        initializer_range=config["initializer_range"], bf16=True,
        activation_checkpointing=bool(job["activation_checkpointing"]),
        tie_word_embeddings=True)


def ds_config(job, chips, rows_per_chip):
    gas = int(job["gradient_accumulation_steps"])
    return {
        "train_batch_size": rows_per_chip * chips * gas,
        "train_micro_batch_size_per_gpu": rows_per_chip,
        "gradient_accumulation_steps": gas,
        "steps_per_print": 10 ** 9,
        **job["ds_config"],
    }


def build(config, job, devices, seed, layers=None, dropout=True,
          rows_per_chip=None):
    """The engine of ``job`` on ``devices`` (a ``data`` mesh over all of
    them), weights made on the device from ``seed`` in one jitted call.

    The weights are born spread over the devices (first dimension the
    device count divides): ``ds.initialize`` copies what it is given
    before it shards, and 1.5B fp32 parameters twice over do not fit
    beside anything on the first chip."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Model

    model = GPT2Model(model_config(config, job, layers, dropout))
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=devices, data=len(devices))
    line = Mesh(np.array(devices), ("d",))

    def spread(leaf):
        dim = next((i for i, n in enumerate(leaf.shape)
                    if n % len(devices) == 0), None)
        return NamedSharding(line, PartitionSpec(
            *([None] * dim + ["d"] if dim is not None else [])))

    key = jax.random.PRNGKey(seed)
    shardings = jax.tree.map(spread, jax.eval_shape(model.init_params, key))
    params = jax.jit(model.init_params, out_shardings=shardings)(key)
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


def flops_per_token(config, job):
    return flops.decoder_train_flops_per_token(
        config["n_embd"], config["n_layer"], job["seq"],
        config["vocab_size"], config.get("n_inner"))


def flash_operand(config, job):
    """[B, H, S, D] of one chip's flash-attention call in ``job``."""
    return (job["batch_per_chip"], config["n_head"], job["seq"],
            config["n_embd"] // config["n_head"])


def reference_params(params):
    """The program's stacked parameter tree under the released
    checkpoints' names, one entry of ``h`` per layer."""
    h = params["h"]
    layers = next(iter(h.values())).shape[0]

    def pair(w, b, i):
        return {"w": h[w][i], "b": h[b][i]}

    return {
        "wte": params["wte"], "wpe": params["wpe"], "ln_f": params["ln_f"],
        "h": [{"ln_1": pair("norm_w", "norm_b", i),
               "attn": {"c_attn": pair("attn_qkvw", "attn_qkvb", i),
                        "c_proj": pair("attn_ow", "attn_ob", i)},
               "ln_2": pair("attn_nw", "attn_nb", i),
               "mlp": {"c_fc": pair("inter_w", "inter_b", i),
                       "c_proj": pair("output_w", "output_b", i)}}
              for i in range(layers)],
    }


def parity(config, job, devices, seed, ids):
    """Engine against reference on ``ids`` [rows, S]: loss, global
    gradient norm and gradient error of the first step, ``job['parity']['layers']`` layers
    at the published widths, dropout off, on the cell's devices and
    layout.  Returns the four numbers and ``ok``; frees the engine."""
    import jax
    import jax.numpy as jnp

    engine = build(config, job, devices, seed,
                   layers=job["parity"]["layers"], dropout=False,
                   rows_per_chip=ids.shape[0] // len(devices))
    first = devices[0]
    ref_loss, ref_grads = jax.jit(
        gpt2_reference.loss_and_grads, static_argnums=(2, 3))(
        jax.device_put(reference_params(engine.params), first),
        jax.device_put(ids, first), config["n_head"],
        config["layer_norm_epsilon"])
    loss = engine.forward(*batch_args(ids))
    # the gradients the grad program handed back for this batch; the
    # engine has no public reader for them
    grads = jax.device_put(reference_params(engine._cached_grads), first)

    @jax.jit
    def compare(ours, ref):
        diff = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, ours, ref)
        return (gpt2_reference.global_norm(ours),
                gpt2_reference.global_norm(ref),
                gpt2_reference.global_norm(diff))

    norm, ref_norm, err = (float(x) for x in compare(grads, ref_grads))
    got = {"loss": float(loss), "ref_loss": float(ref_loss),
           "grad_norm": norm, "ref_grad_norm": ref_norm}
    got["loss_rel"] = abs(got["loss"] - got["ref_loss"]) / got["ref_loss"]
    got["grad_norm_rel"] = abs(norm - ref_norm) / ref_norm
    got["grad_err_rel"] = err / ref_norm
    got["ok"] = bool(math.isfinite(got["loss"])
                     and got["loss_rel"] <= LOSS_RTOL
                     and got["grad_norm_rel"] <= GRAD_NORM_RTOL
                     and got["grad_err_rel"] <= GRAD_ERR_RTOL)
    return got


def program_memory(engine, ids):
    """XLA's own account of the two step programs, per device:
    {program: {argument, output, temp, alias bytes and their sum}}, to
    set beside the allocator's ``peak_bytes_in_use``."""
    import jax

    (batch,), _ = engine._shard_batch((batch_args(ids), {}))
    grad_args = (engine.params, engine.scaler_state, engine._rng, batch)
    grads = jax.eval_shape(engine._grad_fn, *grad_args)[1]
    grads = jax.tree.map(
        lambda g, s: jax.ShapeDtypeStruct(g.shape, g.dtype, sharding=s),
        grads, engine.grad_shardings)
    out = {}
    for name, fn, args in (
            (GRAD_PROGRAM, engine._grad_fn, grad_args),
            (APPLY_PROGRAM, engine._apply_fn,
             (engine.params, engine.opt_state, engine.scaler_state, grads))):
        m = fn.lower(*args).compile().memory_analysis()
        sizes = {"argument": m.argument_size_in_bytes,
                 "output": m.output_size_in_bytes,
                 "temp": m.temp_size_in_bytes,
                 "alias": m.alias_size_in_bytes}
        sizes["live"] = (sizes["argument"] + sizes["output"] + sizes["temp"]
                         - sizes["alias"])
        out[name] = sizes
    return out
