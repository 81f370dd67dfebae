"""Shared layer-stack executor for the model families.

Models keep their transformer layers STACKED (leading layer axis) and run
one compiled body over them.  Two execution modes:

- scan: `lax.scan` — one traced body regardless of depth, fastest compile;
- unrolled: Python loop over the same body — XLA sees the whole depth and
  fuses across layer boundaries (~18 ms/step faster than scan at GPT-2
  124M in a round-2 host-clock ablation on jax 0.4.37; the script is
  gone, git keeps it), at the cost of compile time linear in depth.

The auto policy (`scan_layers=None` in the model configs) unrolls up to
SCAN_LAYERS_AUTO_THRESHOLD layers and scans beyond.

A stack may also run SEVERAL TIMES on the same weights
(`run_layer_recurrence`: a loop of passes around either mode, a per-pass
function between them), the gradient of every weight the sum
over its uses.
"""

import jax
import jax.numpy as jnp

SCAN_LAYERS_AUTO_THRESHOLD = 24


def resolve_use_scan(scan_layers, num_layers: int) -> bool:
    """Shared auto policy for the model configs' `scan_layers=None`."""
    if scan_layers is not None:
        return scan_layers
    return num_layers > SCAN_LAYERS_AUTO_THRESHOLD


def run_layer_stack(body, carry, xs, use_scan: bool, with_ys: bool = False):
    """Run `body(carry, xs_i) -> (carry, y_i)` over the leading axis of xs;
    returns the carry, or with `with_ys` (carry, the y_i stacked)."""
    if use_scan:
        carry, ys = jax.lax.scan(body, carry, xs)
        return (carry, ys) if with_ys else carry
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    if not with_ys:
        return carry
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)


def run_layer_recurrence(body, carry, xs, passes: int, use_scan: bool,
                         after_pass):
    """A stack run ``passes`` times on the SAME stacked weights: each pass
    is ``run_layer_stack(body, carry, xs, use_scan)``, and ``after_pass(
    carry) -> (carry, y)`` takes its output (a final norm, the keep of
    the pass's hidden states); the next pass starts from what it hands
    back.  Returns (carry, the ``passes`` y stacked).  A weight's
    gradient is the sum over its ``passes`` uses, which differentiation
    gives.  The passes are a Python loop, so XLA sees all of them: as an
    outer ``lax.scan`` over one traced pass the v5e ran 8 layers x 4
    passes 1.6% slower for half the compile time (PERF.md section 6,
    PR 45)."""
    ys = []
    for _ in range(passes):
        carry, y = after_pass(run_layer_stack(body, carry, xs, use_scan))
        ys.append(y)
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)
