"""ops/grouped_matmul.py at widths that are half lane tiles and not whole
ones (64, 192, 1,856 = 14.5 x 128, the published width of the experts of
models/nemotron_h.py), as n and as k: the three kernels through the
interpreter against ``jax.lax.ragged_dot`` and its transposes, with
empty groups and rows past the counts' sum; how such a width is cut into
blocks; and the six products of the benchmark cell compiled ahead of
time for the v5e (a compile is not a run)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops import grouped_matmul as gm
from tests.unit.test_ssd_scan import one_chip  # noqa: F401

COUNTS = [37, 0, 91, 5, 0, 3]          # 136 of 256 rows, two groups empty


@pytest.fixture
def interpreter():
    dispatch.set_pallas_interpret(True)
    yield
    dispatch.set_pallas_interpret(False)


def _value_and_grads(x, w, g, counts):
    return jax.value_and_grad(
        lambda x, w: jnp.sum(gm.gmm(x, w, counts).astype(jnp.float32) * g),
        (0, 1))(x, w)


@pytest.mark.parametrize("k,n", [(128, 64), (64, 128), (192, 256),
                                 (256, 192), (128, 1856), (1856, 128),
                                 (192, 64)])
def test_the_kernels_are_the_ragged_product_at_half_tiles(k, n, interpreter,
                                                          monkeypatch):
    """Forward (``gmm_rows``), dx (``gmm_rows_t``: k and n swapped) and dw
    (``gmm_weights``) with the uneven width as the result's columns and
    as the contraction."""
    monkeypatch.setattr(gm, "TILE_ROWS", 64)
    ks = jax.random.split(jax.random.PRNGKey(k + n), 3)
    x = jax.random.normal(ks[0], (256, k))
    w = jax.random.normal(ks[1], (len(COUNTS), k, n)) / np.sqrt(k)
    g = jax.random.normal(ks[2], (256, n))
    counts = jnp.asarray(COUNTS, jnp.int32)
    assert gm._use_pallas(256, k, n) and gm._use_pallas(256, n, k)
    jaxpr = str(jax.make_jaxpr(lambda x, w: _value_and_grads(
        x, w, g, counts))(x, w))
    assert all(name in jaxpr for name in (
        "gmm_rows", "gmm_rows_t", "gmm_weights"))
    assert "ragged_dot" not in jaxpr
    with jax.default_matmul_precision("highest"):
        ours = _value_and_grads(x, w, g, counts)
        out = gm.gmm(x, w, counts)
        dispatch.set_pallas_interpret(False)
        assert not gm._use_pallas(256, k, n)
        want = _value_and_grads(x, w, g, counts)
    assert float(jnp.sum(jnp.abs(out[sum(COUNTS):]))) == 0.0
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(want)):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * float(
            jnp.max(jnp.abs(b)) + 1.0)


@pytest.mark.parametrize("n,cols", [
    (128, 128), (512, 512), (1536, 512), (2048, 512), (2688, 384),
    (640, 128), (64, 64), (192, 192), (1856, 1856), (100, 100), (8, 8)])
def test_column_blocks_answer_for_every_width(n, cols):
    """Whole lane tiles: the parent's blocks (a multiple of 128 up to
    512 that divides n).  Anything else: the whole width, never a walk
    down to zero."""
    assert gm._tile_cols(n) == cols


def test_what_the_kernels_do_not_take_goes_to_the_ragged_product(interpreter):
    """A width that is no multiple of 64, and an uneven width whose whole
    block's double buffer would not fit."""
    assert gm._use_pallas(256, 2688, 1856) and gm._use_pallas(256, 1856, 2688)
    assert gm._use_pallas(256, 2048, 1536)
    assert not gm._use_pallas(256, 128, 96)
    assert not gm._use_pallas(256, 100, 128)
    assert not gm._use_pallas(256, 8192, 1856)
    assert not gm._use_pallas(250, 128, 128)


@pytest.mark.parametrize("product", ["up", "down"])
def test_the_cells_products_compile_for_v5e(product, one_chip, monkeypatch):  # noqa: F811
    """[6144, 2688] x [8, 2688, 1856] and [6144, 1856] x [8, 1856, 2688]
    (the held experts' even share of 16,384 tokens' picks), each with
    its transpose and its weight gradient: ONE Mosaic call each, named
    ``gmm_*``."""
    monkeypatch.setattr(importlib.import_module(
        "deepspeed_tpu.ops.grouped_matmul"), "pallas_available",
        lambda: True)
    rows, groups = 6144, 8
    k, n = {"up": (2688, 1856), "down": (1856, 2688)}[product]

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    counts = on_chip((groups,), jnp.int32)
    for name, fn, args in (
            ("gmm_rows", lambda x, w, c: gm._pallas_rows(x, w, c, False),
             (on_chip((rows, k)), on_chip((groups, k, n)), counts)),
            ("gmm_rows_t", lambda x, w, c: gm._pallas_rows(x, w, c, True),
             (on_chip((rows, n)), on_chip((groups, k, n)), counts)),
            ("gmm_weights", gm._pallas_weights,
             (on_chip((rows, k)), on_chip((rows, n)), counts))):
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1, name
        assert f"{name}/pallas_call" in text
