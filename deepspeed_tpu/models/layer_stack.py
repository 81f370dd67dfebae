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
