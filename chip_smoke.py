"""The quickest proof that the training path still starts on the chip.

    python chip_smoke.py          # on a TPU: GPT-2 124M through ds.initialize
    python chip_smoke.py --tiny   # CPU lane for the tests: toy sizes,
                                  # kernels in the Pallas interpreter

One process, no children.  Phase 1 trains GPT-2 at its published 124M
width (12 layers, hidden 768, 12 heads, 1,024 positions, vocabulary
50,304; B=8, S=1,024, bf16, ZeRO-2, AdamW, dropout at the config's
defaults) on a mesh of ``jax.devices()[:1]`` through ``engine.forward`` /
``backward`` / ``step``.  With four or more devices visible, phase 2
repeats it over ``data=4`` under ZeRO-2 (GSPMD) and under ZeRO-3 (the
streamed layer scan), and checks that state is spread over the four
chips, that the flash kernel runs on each chip's own rows, and that the
first loss on one fixed global batch with dropout off equals the one-chip
loss.  Any failed check raises; nothing is caught on the way to exit 0.

The last line of stdout is ``{"ok": true, "device": {...}}`` with the
device as JAX reports it.  Without ``--tiny`` a platform other than
``tpu`` exits non-zero before anything runs.
"""

import argparse
import gc
import json
import os
import re
import sys
import time

import numpy as np

# The model and the job; --tiny cuts the sizes, never the code path.
FULL = dict(model=dict(), batch_per_chip=8, seq=1024, steps=6, zero={})
# toy leaves all sit under ZeRO's default persistence threshold (counted
# in elements), which would leave nothing to shard
TINY = dict(model=dict(vocab_size=256, n_positions=128, hidden_size=64,
                       num_layers=2, num_heads=2),
            batch_per_chip=2, seq=128, steps=3,
            zero={"stage3_param_persistence_threshold": 0})
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dkdv")
# First-step loss, one chip against four, same global batch, dropout off.
# Every chip computes the same rows with the same kernels as the one-chip
# run, so the forward differs only in how bf16 activations are fused
# (unrolled stack against the streamed scan, rounding single values by
# 2^-9) and in the fp32 order of the mean over shards; averaged over the
# batch's tokens the loss moves orders of magnitude less than one rounding
# (measured on four v5e chips: 7e-7 under ZeRO-2, 1e-6 under ZeRO-3).
PARITY_RTOL = 1e-4
PARITY_LAYERS = 2  # depth cut: three more engines to compile, not six


def say(msg):
    print(msg, flush=True)


def ds_config(size, stage, chips, gas=1):
    batch_per_chip = size["batch_per_chip"]
    return {
        "train_batch_size": batch_per_chip * chips * gas,
        "train_micro_batch_size_per_gpu": batch_per_chip,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-3, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": stage, **size["zero"]},
    }


def build_engine(size, devices, stage, gas=1, dropout=True, layers=None):
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import GPT2Config, GPT2Model

    model_kw = dict(size["model"])
    if layers is not None:
        model_kw["num_layers"] = layers
    if not dropout:
        model_kw.update(embd_dropout=0.0, attn_dropout=0.0,
                        hidden_dropout=0.0)
    model = GPT2Model(GPT2Config(**model_kw))
    ds.reset_mesh_context()
    mesh = ds.initialize_mesh(devices=devices, data=len(devices))
    engine, _, _, _ = ds.initialize(
        model=model, mesh=mesh,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config=ds_config(size, stage, len(devices), gas))
    return engine


def fixed_batch(size, rows, vocab):
    return np.random.RandomState(1234).randint(
        0, vocab, (rows, size["seq"]), dtype=np.int32)


def train_step(engine, ids):
    import jax
    loss = engine.forward(ids)
    engine.backward(loss)
    engine.step()
    jax.block_until_ready((loss, engine.params))
    return float(loss)


def mosaic_flash_calls(text):
    """{kernel: [operand shapes of each call]} for the flash kernels in a
    compiled program's text: Mosaic custom calls, told apart by the
    kernel name in their op_name scope."""
    calls = {k: [] for k in FLASH_KERNELS}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        scope = re.search(r'op_name="[^"]*?/(flash_\w+)/pallas_call', line)
        if scope is None:
            continue
        layouts = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                            line).group(1)
        calls[scope.group(1)].append(
            [tuple(int(d) for d in dims.split(",") if d)
             for dims in re.findall(r"\w+\[([\d,]*)\]", layouts)])
    return calls


def traced_flash_calls(jaxpr):
    """The same from a traced program: pallas_call equations by name."""
    from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
    calls = {k: [] for k in FLASH_KERNELS}
    for eqn in (ctx.eqn for ctx in iter_eqns(jaxpr)):
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]].append(
                [tuple(v.aval.shape) for v in eqn.invars])
    return calls


def flash_calls(engine, ids, interpret):
    """The flash kernels in the grad program the engine dispatches.  On
    the chip they are read from the COMPILED text, so a dispatcher that
    quietly took the XLA path shows up as zero calls; the interpreter
    leaves no custom call behind, so the CPU lane reads the traced
    program instead."""
    import jax
    (batch,), _ = engine._shard_batch(((ids,), {}))
    args = (engine.params, engine.scaler_state, engine._rng, batch)
    if interpret:
        return traced_flash_calls(jax.make_jaxpr(engine._grad_fn)(*args))
    return mosaic_flash_calls(
        engine._grad_fn.lower(*args).compile().as_text())


def check_flash_calls(calls, cfg, size, streamed):
    counts = {k: len(v) for k, v in calls.items()}
    if streamed:
        # the layer groups run inside lax.scan bodies, so the text holds
        # one call per body, not per layer
        if min(counts.values()) < 1:
            raise AssertionError(f"flash kernels missing: {counts}")
    elif any(n != cfg.num_layers for n in counts.values()):
        raise AssertionError(
            f"expected {cfg.num_layers} calls of each flash kernel "
            f"(2 x layers in all), found {counts}")
    # each chip's kernel takes that chip's rows: a q/k/v gather in front
    # of it would show as the global batch in the operand
    local = (size["batch_per_chip"], cfg.num_heads, size["seq"],
             cfg.hidden_size // cfg.num_heads)
    for kernel, operand_sets in calls.items():
        for shapes in operand_sets:
            if local not in shapes:
                raise AssertionError(
                    f"{kernel}: no per-chip operand {local} in {shapes}")
    return counts


def state_bytes(engine, devices):
    """Where the parameters and the optimizer state actually sit:
    {tree: (total bytes, [bytes on each device])}, from the shards."""
    import jax
    out = {}
    for name, tree in (("params", engine.params),
                       ("opt_state", engine.opt_state)):
        held, total = dict.fromkeys(devices, 0), 0
        for leaf in jax.tree.leaves(tree):
            if not hasattr(leaf, "addressable_shards"):
                continue
            total += leaf.nbytes
            for shard in leaf.addressable_shards:
                held[shard.device] += shard.data.nbytes
        out[name] = (total, [held[d] for d in devices])
    return out


def memory(devices, key):
    """One allocator statistic from EVERY device (None where the backend
    reports none, as on the CPU)."""
    return [(d.memory_stats() or {}).get(key) for d in devices]


def flops_per_token(cfg, seq):
    """Training FLOPs a token that the MFU line divides by: the
    benchmark's own count (perf/flops.py, causal attention at half the
    square), so the smoke test's MFU and the ledger's `mfu_pct` are one
    arithmetic.  Imported here, after the package has been: the script
    runs from the checkout root, where `perf/` sits beside it."""
    from perf.flops import decoder_train_flops_per_token
    return decoder_train_flops_per_token(
        cfg.hidden_size, cfg.num_layers, seq, cfg.vocab_size,
        intermediate=cfg.intermediate_size)


def mib(values):
    return "[" + ", ".join("n/a" if v is None else f"{v / 2**20:,.0f}"
                           for v in values) + "] MiB"


def run_phase(name, size, devices, stage, interpret, peaks):
    """Train ``steps`` steps on one fixed batch through the engine's entry
    points and check what came out.  Raises on any failed check."""
    chips = len(devices)
    say(f"--- phase {name}: {chips} chip(s), ZeRO-{stage}")
    t0 = time.perf_counter()
    engine = build_engine(size, devices, stage)
    cfg = engine.module.config
    ids = fixed_batch(size, size["batch_per_chip"] * chips, cfg.vocab_size)
    calls = flash_calls(engine, ids, interpret)
    # set while the grad program is traced: the layer scan really streamed
    plan = getattr(engine._zero3_stream, "last_plan", None)
    counts = check_flash_calls(calls, cfg, size, streamed=plan is not None)
    in_use = memory(devices, "bytes_in_use")
    state = state_bytes(engine, devices)
    losses = [train_step(engine, ids)]
    setup_s = time.perf_counter() - t0
    step_s = []
    for _ in range(size["steps"] - 1):
        t = time.perf_counter()
        losses.append(train_step(engine, ids))
        step_s.append(time.perf_counter() - t)
    peak = memory(devices, "peak_bytes_in_use")

    if peaks is None:
        say("    platform=cpu: steps not timed, a CPU time is no device "
            "metric")
    else:
        step = float(np.median(step_s))
        tokens = size["batch_per_chip"] * chips * size["seq"]
        mfu = (tokens / step * flops_per_token(cfg, size["seq"])
               / (chips * peaks["bf16_tflops"] * 1e12))
        say(f"    set-up (init + compile + first step): {setup_s:.1f} s")
        say(f"    step: median {step * 1e3:.1f} ms over {len(step_s)} "
            f"(min {min(step_s) * 1e3:.1f}, max {max(step_s) * 1e3:.1f}); "
            f"{tokens / step:,.0f} tokens/s; MFU {mfu:.1%} of "
            f"{peaks['bf16_tflops']:.0f} TFLOP/s/chip")
    say(f"    loss: first {losses[0]:.4f}, last {losses[-1]:.4f} "
        f"({len(losses)} steps, fixed batch)")
    say(f"    flash kernels in the grad program: {counts}")
    if plan is not None:
        say(f"    ZeRO-3 stream: {plan.num_layers} layers in groups of "
            f"{plan.layers_per_step}, prefetch {plan.prefetch}")
    for tree, (total, held) in state.items():
        say(f"    {tree}: {mib([total])} in all, per device {mib(held)}")
    say(f"    bytes in use after init, per device: {mib(in_use)}")
    say(f"    peak bytes per device (process high-water): {mib(peak)}")

    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{name}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{name}: loss did not fall: {losses}")
    if chips > 1:
        if (plan is not None) != (stage == 3):
            raise AssertionError(
                f"{name}: stage {stage} on {chips} chips, streamed="
                f"{plan is not None}")
        # ZeRO-2 shards the optimizer state, ZeRO-3 the parameters too;
        # the few leaves too small to split stay whole
        want = {"opt_state": 1 / chips,
                "params": 1 / chips if stage == 3 else 1.0}
        for tree, share in want.items():
            total, held = state[tree]
            if max(held) > share * total * 1.05:
                raise AssertionError(
                    f"{name}: a device holds {max(held) / total:.3f} of "
                    f"{tree}, stage {stage} should leave {share:.3f}")
        # and the allocator of every chip, not only the first, agrees
        # that it holds its part
        for i, used in enumerate(in_use):
            part = state["params"][1][i] + state["opt_state"][1][i]
            if used is not None and used < 0.9 * part:
                raise AssertionError(
                    f"{name}: device {i} reports {used} bytes in use, its "
                    f"shards add up to {part}")
    del engine
    gc.collect()


def parity_loss(size, devices, stage):
    """First-step loss on the fixed four-chip global batch, dropout off,
    depth cut to PARITY_LAYERS.  One chip walks the batch as four
    accumulation micro-steps at the initial weights."""
    chips = len(devices)
    gas = 4 // chips
    engine = build_engine(size, devices, stage, gas=gas, dropout=False,
                          layers=PARITY_LAYERS)
    ids = fixed_batch(size, size["batch_per_chip"] * 4,
                      engine.module.config.vocab_size)
    losses = []
    for micro in np.split(ids, gas):
        loss = engine.forward(micro)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    del engine
    gc.collect()
    return float(np.mean(losses))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU lane for the tests: toy sizes, kernels in "
                         "the Pallas interpreter, 8 simulated devices")
    args = ap.parse_args()
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        # S=128 sits under the auto crossover to XLA attention
        os.environ["DS_FLASH_MIN_SEQ"] = "0"

    import jax
    from deepspeed_tpu.ops.dispatch import set_pallas_interpret
    from deepspeed_tpu.utils.chip import (device_peaks, device_summary,
                                          enable_compile_cache)

    device = device_summary()
    say(f"jax {jax.__version__}  platform={device['platform']}  "
        f"device_kind={device['kind']}  devices={device['count']}")
    want = "cpu" if args.tiny else "tpu"
    if device["platform"] != want:
        sys.exit(f"chip_smoke.py: platform={device['platform']}, need "
                 f"{want} — this script proves the program on the chip; "
                 "the CPU lane is `chip_smoke.py --tiny`")
    size, peaks = (TINY, None) if args.tiny else (FULL, device_peaks(
        device["kind"]))
    set_pallas_interpret(args.tiny)
    cache_dir = enable_compile_cache()

    def cache_entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    entries_before = cache_entries()
    say(f"compile cache: {cache_dir} ({entries_before} entries)")

    devices = jax.devices()
    t0 = time.perf_counter()
    run_phase("one-chip ZeRO-2", size, devices[:1], 2, args.tiny, peaks)
    if len(devices) >= 4:
        four = devices[:4]
        run_phase("four-chip ZeRO-2", size, four, 2, args.tiny, peaks)
        run_phase("four-chip ZeRO-3 streamed", size, four, 3, args.tiny,
                  peaks)
        say(f"--- phase loss parity: one chip against four, dropout off, "
            f"{PARITY_LAYERS} layers")
        one = parity_loss(size, devices[:1], 2)
        for stage in (2, 3):
            got = parity_loss(size, four, stage)
            say(f"    ZeRO-{stage} on four chips {got:.6f}, one chip "
                f"{one:.6f}, relative difference {abs(got - one) / one:.1e}"
                f" (tolerance {PARITY_RTOL:.0e})")
            if not abs(got - one) <= PARITY_RTOL * abs(one):
                raise AssertionError(
                    f"ZeRO-{stage} first loss {got} != one-chip {one}")
    else:
        say(f"--- four-chip phases not run: {len(devices)} device(s) "
            "visible, they need 4")
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s; compile "
        f"cache entries {entries_before} -> {cache_entries()}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
