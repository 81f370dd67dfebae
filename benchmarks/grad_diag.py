"""One-step gradient A/B: Pallas kernels vs forced-XLA, same batch.

Round-4 convergence triage (git keeps the notes): GPT-2 124M on the chip
plateaus at the support entropy ln(4096) — it never learns even
p(next|prev), a task the residual path alone (embedding -> FFN -> logits)
can solve.  The dropout-OFF probe plateaus too, so the in-kernel dropout
is exonerated.  Remaining suspects are the Pallas ops at flagship shapes
(flash attention S=1024, fused CE) vs bf16 itself.

This tool discriminates *which op and which direction*:
  - run the SAME fixed Markov batch through the model twice in fresh
    subprocesses: DS_FORCE_XLA_OPS=0 (production kernels) and =1 (XLA
    reference ops), identical params/seed;
  - if the LOSSES differ -> a forward kernel is wrong at these shapes;
  - if losses agree but per-leaf grad cosines are low -> a backward rule
    is wrong; the leaf pattern (attn vs mlp vs wte) names the op.
On CPU both paths are XLA, so cosines ~1.0 give the null calibration.

Emits one JSON line: worst-leaf cosine + losses + per-group summaries.
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

_CHILD = r"""
import json, os, sys
import numpy as np
import jax, jax.numpy as jnp

sys.path.insert(0, "@REPO@")
sys.path.insert(0, "@REPO@/benchmarks")
from deepspeed_tpu.utils.chip import enable_compile_cache
enable_compile_cache()  # the second child must not recompile 124M
from convergence_run import MarkovLanguage, BATCH, SEQ
from deepspeed_tpu.models import GPT2Config, GPT2Model

lang = MarkovLanguage()
ids = lang.sample(BATCH, SEQ, np.random.RandomState(4242))

cfg = GPT2Config(n_positions=SEQ, bf16=bool(int(os.environ.get(
    "DS_DIAG_BF16", "1"))), embd_dropout=0.0, attn_dropout=0.0,
    hidden_dropout=0.0)
model = GPT2Model(cfg)
params = model.init_params(jax.random.PRNGKey(0))

loss, grads = jax.jit(jax.value_and_grad(
    lambda p: model.loss(p, None, jnp.asarray(ids))))(params)
flat = jax.tree_util.tree_flatten_with_path(grads)[0]
out_dir = sys.argv[1]
manifest = {}
for path, leaf in flat:
    name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)
    arr = np.asarray(leaf, np.float32)
    manifest[name] = {"norm": float(np.linalg.norm(arr))}
    # fp32 on disk: fp16 would underflow tiny-magnitude leaves to zero in
    # BOTH children and report a spurious 0.0 cosine (~500 MB tmp total)
    np.save(os.path.join(out_dir, name.replace("/", "__") + ".npy"), arr)
with open(os.path.join(out_dir, "manifest.json"), "w") as f:
    json.dump({"loss": float(loss), "leaves": manifest,
               "platform": jax.devices()[0].platform}, f)
print("child done", float(loss))
"""


def run_child(force_xla: bool, out_dir: str):
    env = dict(os.environ)
    env["DS_FORCE_XLA_OPS"] = "1" if force_xla else "0"
    code = _CHILD.replace("@REPO@", _REPO)
    # 900 s/child keeps 2 children + the ~1 GB npy comparison inside the
    # post-session script's 2400 s stage budget (chip children run ~3
    # min); the CPU leg overrides — 124M fwd+bwd on a contended host
    # CPU can exceed 900 s in compile alone
    child_t = int(os.environ.get("DS_DIAG_CHILD_TIMEOUT", "900"))
    proc = subprocess.run([sys.executable, "-c", code, out_dir],
                          capture_output=True, text=True, timeout=child_t,
                          env=env, cwd=_REPO)
    if proc.returncode != 0:
        raise RuntimeError(f"diag child (force_xla={force_xla}) failed:\n"
                           f"{proc.stderr[-3000:]}")
    with open(os.path.join(out_dir, "manifest.json")) as f:
        return json.load(f)


def group_of(name: str) -> str:
    if "attn" in name:
        return "attn"
    if "mlp" in name:
        return "mlp"
    for emb in ("wte", "wpe"):
        if emb in name:
            return emb
    return "other"


def compare_dirs(da, db, label_a="pallas", label_b="xla"):
    with open(os.path.join(da, "manifest.json")) as f:
        ma = json.load(f)
    with open(os.path.join(db, "manifest.json")) as f:
        mb = json.load(f)
    rows = []
    for name, meta in ma["leaves"].items():
        a = np.load(os.path.join(
            da, name.replace("/", "__") + ".npy")).astype(np.float32)
        b = np.load(os.path.join(
            db, name.replace("/", "__") + ".npy")).astype(np.float32)
        na, nb = np.linalg.norm(a), np.linalg.norm(b)
        # manifest norm = in-child fp32 norm; catches npy round-trip
        # corruption (the fp16 underflow class of bug) loudly
        if not np.isclose(na, meta["norm"], rtol=1e-3, atol=1e-6):
            raise RuntimeError(
                f"npy round-trip norm mismatch for {name}: "
                f"{na} vs manifest {meta['norm']}")
        cos = float((a * b).sum() / max(na * nb, 1e-30))
        ratio = float(na / max(nb, 1e-30))
        rows.append((name, cos, ratio, float(na), float(nb)))
    groups = {}
    for name, cos, ratio, na, nb in rows:
        groups.setdefault(group_of(name), []).append((cos, ratio))
    summary = {g: {"min_cos": round(min(c for c, _ in v), 4),
                   "med_ratio": round(float(np.median([r for _, r in v])), 4)}
               for g, v in groups.items()}
    worst = min(rows, key=lambda r: r[1])
    print(json.dumps({
        "metric": f"grad_diag_{label_a}_vs_{label_b}_worst_leaf_cosine",
        "value": round(worst[1], 4),
        "unit": "cosine",
        "worst_leaf": worst[0],
        f"worst_leaf_norms_{label_a}_{label_b}": [round(worst[3], 6),
                                                  round(worst[4], 6)],
        f"loss_{label_a}": round(ma["loss"], 6),
        f"loss_{label_b}": round(mb["loss"], 6),
        "loss_delta": round(abs(ma["loss"] - mb["loss"]), 6),
        "groups": summary,
        "platforms": [ma["platform"], mb["platform"]],
    }), flush=True)


def main(argv=None):
    """Default: run both children in temp dirs and compare.

    --keep DIR   persist child outputs to DIR/pallas and DIR/xla (so a
                 later cross-PLATFORM compare can reuse them — the
                 params and batch are seed-deterministic and threefry is
                 platform-independent, so a CPU child and a chip child
                 see identical inputs)
    --compare A B [--labels la lb]   skip running; compare two saved
                 child dirs (e.g. chip pallas vs CPU xla)
    """
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--keep", default=None)
    ap.add_argument("--compare", nargs=2, default=None)
    ap.add_argument("--labels", nargs=2, default=None)
    args = ap.parse_args(argv)

    if args.compare:
        la, lb = args.labels or ("a", "b")
        compare_dirs(args.compare[0], args.compare[1], la, lb)
        return

    if args.keep:
        da = os.path.join(args.keep, "pallas")
        db = os.path.join(args.keep, "xla")
        os.makedirs(da, exist_ok=True)
        os.makedirs(db, exist_ok=True)
        run_child(False, da)
        run_child(True, db)
        compare_dirs(da, db)
        return

    with tempfile.TemporaryDirectory() as da, \
            tempfile.TemporaryDirectory() as db:
        run_child(False, da)
        run_child(True, db)
        compare_dirs(da, db)


if __name__ == "__main__":
    main()
