"""The benchmark's command: one cell, one run, one process, no children.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``perf/workloads/<cell>.json`` and, by the names it gives, the
configuration, the traffic mix, the model family and the per-layer
readers; this file holds none of those names.  Refuses any platform but
the one asked for (``tpu``) and fewer devices than the cell's ``chips``.
Set-up: traffic pool and weights from ``--seed``, parity of a shallow
engine with the family's plain reference, the timed engine through
``deepspeed_tpu.initialize``, two warm-up steps.  Then the user's loop,
``engine.forward / backward / step`` on a fresh batch each step with one
step in flight (after dispatching step k the host waits for the loss of
step k-1, as a script that logs it does), until ``--seconds`` have
passed; the window closes with ``block_until_ready`` on the loss and the
parameters.  A step's time is the difference between successive ready
times on the host clock.

``--trace 1`` is a run of its own: the same loop with ``jax.profiler``
on for a few steps in the middle, the benchmark's own host spans around
batch selection and the three engine calls, and the trace reduced by
``perf/trace_reduce.py`` and the readers in ``perf/layer_metrics/``.

The last line of standard output is the one JSON object the driver
reads; everything else (parity, losses, cache entries, MFU, the
programs' memory) is on earlier lines.
"""

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WARMUP_STEPS = 2
TRACED_STEPS = 5
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def read_json(root, kind, name):
    with open(os.path.join(root, "perf", kind, name + ".json")) as f:
        return json.load(f)


def load_module(root, kind, name):
    """``<root>/perf/<kind>/<name>.py``, found by the name a data file
    gives; loaded by path, so a new file is all a later PR adds."""
    path = os.path.join(root, "perf", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perf_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cache_dir(root):
    """Where compiled programs are kept: ``JAX_COMPILATION_CACHE_DIR``
    where it is set (JAX reads it itself), else a fixed directory inside
    the checkout.  The path is part of a cache entry's key, so it carries
    no pid, time or temporary name."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


class CompileCounter:
    """Counts programs JAX compiled or fetched from its cache while
    ``open``: both mean a shape the warm-up did not cover."""

    def __init__(self):
        import jax.monitoring
        self.count, self.open = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.open and event == COMPILE_EVENT:
            self.count += 1


def say(text):
    print(text, flush=True)


def run_cell(workload, seed, seconds, trace, root=ROOT, platform="tpu",
             started=None):
    """One run of ``workload``; returns the result object.  ``root`` and
    ``platform`` are for the tests, which run a throw-away cell from a
    copy of this directory on the CPU; the command passes neither."""
    started = time.perf_counter() if started is None else started
    cell = read_json(root, "workloads", workload)
    config = read_json(root, "configs", cell["config"])
    traffic = read_json(root, "traffic", cell["traffic"])
    chips = int(cell["chips"])
    job = {**cell["job"], "batch_per_chip": int(traffic["batch_per_chip"]),
           "seq": int(traffic["seq"])}
    if root not in sys.path:
        sys.path.insert(0, root)

    import jax
    import numpy as np
    from perf import trace_reduce
    from perf.peaks import peaks

    cache = cache_dir(root)
    # tiny programs too, so that a second run adds no entry at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"jax {jax.__version__}  {device}  cell={workload} seed={seed}")
    if device["platform"] != platform:
        raise SystemExit(f"perf/run.py: platform={device['platform']}, "
                         f"this benchmark measures {platform} only")
    if len(devices) < chips:
        raise SystemExit(f"perf/run.py: {workload} needs {chips} chip(s), "
                         f"JAX sees {len(devices)}")
    peak = peaks(device["kind"]) if platform == "tpu" else None
    devices = devices[:chips]
    entries_before = cache_entries(cache)
    say(f"compile cache: {cache} ({entries_before} entries)")

    family = load_module(root, "families", config["family"])
    generator = load_module(root, "traffic", traffic["generator"])
    rows = job["batch_per_chip"] * chips
    gas = int(job["gradient_accumulation_steps"])
    tokens_per_step = rows * job["seq"] * gas
    pool = generator.make(traffic, rows, family.vocab_rows(config), seed)
    marks = [("imports, devices, traffic", time.perf_counter())]

    # 1. parity with the plain reference, before the timed engine exists
    parity = family.parity(
        config, job, devices, seed,
        pool[0][:job["parity"]["rows_per_chip"] * chips])
    say("parity " + json.dumps(parity))
    gc.collect()
    marks.append(("parity", time.perf_counter()))

    engine = family.build(config, job, devices, seed)
    marks.append(("weights and engine", time.perf_counter()))
    span = (lambda name: jax.profiler.TraceAnnotation(
        trace_reduce.SPAN_PREFIX + name)) if trace else (
        lambda name: contextlib.nullcontext())
    taken = 0

    def one_step():
        """One optimizer step on the next batches of the pool; returns
        its last micro-batch's loss, still on the device."""
        nonlocal taken
        for _ in range(gas):
            with span("make_batch"):
                args = family.batch_args(pool[taken % len(pool)])
            taken += 1
            with span("forward"):
                loss = engine.forward(*args)
            with span("backward"):
                engine.backward(loss)
            with span("step"):
                engine.step()
        return loss

    losses = []
    for _ in range(WARMUP_STEPS):
        losses.append(one_step())
        jax.block_until_ready((losses[-1], engine.params))
    setup_s = time.perf_counter() - started
    marks.append((f"{WARMUP_STEPS} warm-up steps", time.perf_counter()))
    entries_warm = cache_entries(cache)
    say(f"set-up {setup_s:.2f} s: " + ", ".join(
        f"{name} {at - before:.2f}" for (name, at), before in zip(
            marks, [started] + [m[1] for m in marks])))

    def segment(done):
        """The loop with one step in flight until ``done(steps so far)``;
        returns (the times a loss was found ready, the time all was)."""
        ready, pending, n = [], None, 0
        while not done(n):
            loss = one_step()
            n += 1
            if pending is not None:
                pending.block_until_ready()
                ready.append(time.perf_counter())
            losses.append(loss)
            pending = loss
        jax.block_until_ready((pending, engine.params))
        return ready, time.perf_counter()

    compiles = CompileCounter()
    compiles.open = True
    t0 = time.perf_counter()

    def until(share):
        return lambda n: n >= 3 and time.perf_counter() - t0 >= share * seconds

    step_s = []
    if trace:
        trace_dir = os.path.join(root, ".perf_trace", workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        step_s += list(np.diff(segment(until(0.4))[0]))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # it slows the host it measures
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            segment(lambda n: n >= TRACED_STEPS)
        finally:
            jax.profiler.stop_trace()
    ready, t1 = segment(until(1.0))
    step_s += list(np.diff(ready))
    compiles.open = False
    attempted = len(losses) - WARMUP_STEPS
    host = {"tokens_per_s": (tokens_per_step / statistics.fmean(step_s)
                             if trace else
                             attempted * tokens_per_step / (t1 - t0)),
            "step_ms_p50": 1e3 * statistics.median(step_s)}

    # 2. the timed engine's own losses
    values = [float(v) for v in jax.device_get(losses)]
    failed = sum(not math.isfinite(v) for v in values[WARMUP_STEPS:])
    vocab = family.vocab_rows(config)
    verdict = loss_verdict(values, cell["loss_check"],
                           generator.entropy(traffic, vocab),
                           generator.batch_sd(traffic, vocab,
                                              rows * job["seq"]))
    say("losses " + " ".join(f"{v:.4f}" for v in values))
    say(f"window: {attempted} steps in {t1 - t0:.3f} s, {len(step_s)} step "
        f"times, min {1e3 * min(step_s):.2f} median "
        f"{host['step_ms_p50']:.2f} max {1e3 * max(step_s):.2f} ms; "
        f"compilations in the window: {compiles.count}")
    say(f"compile cache entries: {entries_before} at start, {entries_warm} "
        f"after warm-up, {cache_entries(cache)} at the end")
    if peak is not None:
        need = family.flops_per_token(config, job)
        say("MFU {:.2f}% of {:.0f} TFLOP/s/chip at {:.4f} GFLOP/token"
            .format(100 * host["tokens_per_s"] * need
                    / (chips * peak["bf16_flops"]),
                    peak["bf16_flops"] / 1e12, need / 1e9))
    stats = [d.memory_stats() or {} for d in devices]
    memory = [s.get("peak_bytes_in_use") for s in stats]
    say(f"peak_bytes_in_use per device: {memory}; memory_stats of the "
        f"first: {stats[0]}")
    device["count"] = chips
    device["memory_peak_bytes"] = max((m for m in memory if m), default=0)

    parts = {"parity": bool(parity["ok"]), "finite": failed == 0,
             "loss check": verdict["ok"], "compiles": compiles.count == 0}
    a, b = cell["loss_check"]["steps"]
    say("correct: " + ", ".join(
        f"{name} {'held' if held else 'FAILED'}"
        for name, held in parts.items())
        + f"; loss check: median of steps {a} to {b} "
        + ("not reached" if verdict["settled"] is None
           else format(verdict["settled"], ".4f"))
        + f" against limit {verdict['limit']:.4f}, lowest of {len(values)} "
        f"losses {verdict['lowest']:.4f} against floor "
        f"{verdict['floor']:.4f}")
    result = {"correct": all(parts.values()),
              "attempted": attempted, "failed": failed}
    if trace:
        say("program memory " + json.dumps(
            family.program_memory(engine, pool[0])))
        xplane = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True)[0]
        reduced = trace_reduce.load(xplane)
        device["busy_s"], device["window_s"] = trace_reduce.device_busy(
            reduced)
        if platform == "tpu" and not device["busy_s"] > 0:
            raise SystemExit("perf/run.py: the trace shows no operation on "
                             f"the device ({xplane})")
        info = {"steps_traced": TRACED_STEPS, "chips": chips, "job": job,
                "config": config, "family": family, "peak": peak,
                "host": host,
                "compiles_in_window": compiles.count,
                "memory_peak_bytes": device["memory_peak_bytes"],
                "memory_stats": stats}
        metrics = {}
        for name in cell["per_layer"]:
            reader = load_module(root, "layer_metrics", name)
            value = reader.reduce(reduced, info)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": reader.UNIT}
        result["breakdown"] = trace_reduce.breakdown(reduced)
    else:
        metrics = {
            "tokens_per_s": {"value": host["tokens_per_s"],
                             "unit": "tokens/s"},
            "step_ms_p50": {"value": host["step_ms_p50"], "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["metrics"] = metrics
    result["device"] = device
    return result


FLOOR_SDS = 6


def loss_verdict(values, check, entropy, sd):
    """The loss check on ``values``, every step's loss from the first
    warm-up step on, under a cell's ``check`` = {"steps": [a, b],
    "rise": r}.  ``entropy`` is the traffic's, nats a token, and ``sd``
    the scatter of one reported loss about its expectation; both come
    from the traffic's generator, neither from the program.  Two
    conditions:

    floor    no loss is under ``entropy - FLOOR_SDS * sd``.  Derived, not
             fitted: fresh independent ids cannot be predicted better,
             so a loss below it saw its targets or is no mean
             cross-entropy.
    settled  the median of steps a to b (1-based, warm-up counted) is at
             most ``entropy + rise``: training has come down to the
             plateau.  A median, so that a spike or two in the run of
             steps refuse nobody; fixed indices, so that a seed's
             statistic is one number however fast the step is.  A run
             that does not reach step b is not judged sound.
    """
    a, b = check["steps"]
    floor = entropy - FLOOR_SDS * sd
    limit = entropy + check["rise"]
    lowest = min(values)
    settled = statistics.median(values[a - 1:b]) if len(values) >= b else None
    return {"settled": settled, "limit": limit, "lowest": lowest,
            "floor": floor,
            "ok": bool(all(map(math.isfinite, values))
                       and settled is not None and settled <= limit
                       and lowest >= floor)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), started=_PROCESS_START)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
