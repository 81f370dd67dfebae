"""The grouped matrix product over ragged counts (ops/grouped_matmul.py),
both forms, against a Python loop over the groups: forward and both
gradients, with empty groups, one group holding every row, rows past
the counts' sum, and group boundaries inside and on tile boundaries."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops import grouped_matmul as gm


@pytest.fixture(params=["xla", "pallas"])
def form(request):
    """Both forms behind the one switch, the backend: the interpreter
    makes the dispatcher take the kernels on the CPU."""
    dispatch.set_pallas_interpret(request.param == "pallas")
    yield request.param
    dispatch.set_pallas_interpret(False)


def _loop(x, w, counts):
    """Row r of group g is x[r] @ w[g]; rows past the sum are zero."""
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    start = 0
    for g, count in enumerate(counts):
        rows = slice(start, start + count)
        out = out.at[rows].set(x[rows] @ w[g])
        start += count
    return out


COUNTS = {
    "uneven": [37, 0, 91, 5, 0, 3],
    "one group holds every row": [0, 0, 256, 0],
    "all empty": [0, 0, 0],
    "on tile boundaries": [64, 64, 0, 128],
    "full": [100, 60, 96],
    "a single row each": [1, 1, 1, 1, 1],
}


def _operands(counts, rows=256, k=128, n=256):
    ks = jax.random.split(jax.random.PRNGKey(sum(counts) + len(counts)), 3)
    return (jax.random.normal(ks[0], (rows, k)),
            jax.random.normal(ks[1], (len(counts), k, n)) / np.sqrt(k),
            jax.random.normal(ks[2], (rows, n)))


@pytest.mark.parametrize("case", list(COUNTS))
def test_forward_and_gradients_match_a_loop_over_groups(case, form,
                                                        monkeypatch):
    counts = COUNTS[case]
    # 64-row tiles, so that 256 rows are four tiles
    monkeypatch.setattr(gm, "TILE_ROWS", 64)
    x, w, g = _operands(counts)
    count_array = jnp.asarray(counts, jnp.int32)
    with jax.default_matmul_precision("highest"):
        ours = jax.value_and_grad(
            lambda x, w: jnp.sum(gm.gmm(x, w, count_array) * g),
            (0, 1))(x, w)
        want = jax.value_and_grad(
            lambda x, w: jnp.sum(_loop(x, w, counts) * g), (0, 1))(x, w)
        out = gm.gmm(x, w, count_array)
    assert float(jnp.sum(jnp.abs(out[sum(counts):]))) == 0.0
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(want)):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * float(
            jnp.max(jnp.abs(b)) + 1.0)


def test_work_items_cover_every_tile_once_and_every_group():
    counts = jnp.asarray([37, 0, 91, 5, 0, 3], jnp.int32)
    items = jax.tree.map(np.asarray, gm.work_items(counts, 256, 64))
    assert len(items["group"]) == 256 // 64 + 6
    real = items["group"] < 6
    # every group is visited, the empty ones too; tiles never go back
    assert set(items["group"][real]) == set(range(6))
    assert (np.diff(items["out_tile"]) >= 0).all()
    # each of the four tiles is zeroed exactly once
    assert sorted(items["out_tile"][items["first"] == 1]) == [0, 1, 2, 3]
    # each group's weight block is zeroed exactly once
    assert items["first_g"].sum() == 6


def test_bfloat16_rows_accumulate_in_float32(form):
    counts = jnp.asarray([100, 28, 0, 128], jnp.int32)
    x, w, _ = _operands([100, 28, 0, 128])
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    want = _loop(xb.astype(jnp.float32), wb.astype(jnp.float32),
                 [100, 28, 0, 128])
    got = gm.gmm(xb, wb, counts)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.05


def test_shapes_that_do_not_fit_are_refused():
    with pytest.raises(ValueError):
        gm.gmm(jnp.zeros((8, 128)), jnp.zeros((3, 64, 128)),
               jnp.zeros((3,), jnp.int32))
    with pytest.raises(ValueError):
        gm.gmm(jnp.zeros((8, 128)), jnp.zeros((3, 128, 128)),
               jnp.zeros((2,), jnp.int32))
