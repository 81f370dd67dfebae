"""The dropless layer's walk over chunks (``moe/dropless.py``): row
buffers of the held experts' even share of the picks, as many trips as
the routed rows take, value and every gradient against the plain form (a
dense loop over the experts), and no array of the worst case's size in
what it lowers to.  Float32 on the CPU, the grouped product's kernels
through the Pallas interpreter."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.extend.core import Var

from deepspeed_tpu.analysis.jaxpr_walk import (aval_bytes, iter_eqns,
                                               sub_jaxprs)
from deepspeed_tpu.moe.dropless import (DroplessMoE, dispatch_capacity,
                                        dispatch_chunks)
from deepspeed_tpu.ops import dispatch

HIDDEN, EXPERTS, K, FF = 128, 16, 4, 128
HELD = (4, 4)
TOKENS = 256


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(dispatch, "_interpret", True)


def _layer(held=HELD):
    return DroplessMoE(HIDDEN, EXPERTS, K, FF, FF, scale=2.5,
                       experts_held=held, init_std=0.1)


def _inputs(layer, tokens, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (layer.init_params(keys[0]),
            jax.random.normal(keys[1], (tokens, HIDDEN), jnp.float32),
            jax.random.normal(keys[2], (tokens, HIDDEN), jnp.float32))


def _picks(tokens, here, seed=0):
    """int32 [tokens, K] of distinct experts a token, ``here[t]`` of them
    among the held ones."""
    rng = np.random.default_rng(seed)
    first, count = HELD
    held = np.arange(first, first + count)
    others = np.setdiff1d(np.arange(EXPERTS), held)
    rows = [rng.permutation(np.concatenate([
        rng.choice(held, n, replace=False),
        rng.choice(others, K - n, replace=False)])) for n in here]
    return jnp.asarray(np.stack(rows), jnp.int32)


def plain(layer, params, x, picks=None):
    """The layer by a dense loop over the held experts, every expert on
    every token, nothing sorted, gathered or grouped."""
    scores = jax.nn.sigmoid(x @ params["router"])
    if picks is None:
        _, picks = jax.lax.top_k(scores, layer.k)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weights = layer.scale * picked / jnp.sum(picked, -1, keepdims=True)
    first, count = layer.experts_held
    y = layer.shared.apply(params["shared"], x)
    for e in range(count):
        mine = jnp.sum(weights * (picks == first + e), axis=-1)
        y = y + mine[:, None] * layer.expert.apply(
            jax.tree.map(lambda w: w[e], params["experts"]), x)
    return y


def _close(ours, want, rtol=2e-4):
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(want),
                    strict=True):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) <= rtol * float(
            jnp.max(jnp.abs(b)) + 1e-9)


CASES = {
    # name: (held range, tokens, picks held a token or None, trips)
    "routed rows under the capacity": (HELD, TOKENS, "a few", 1),
    "the router's own picks": (HELD, TOKENS, None, None),
    "every pick on a held expert": (HELD, TOKENS, "all", 4),
    "no pick on a held expert": (HELD, TOKENS, "none", 0),
    "every expert held": (None, TOKENS, None, 1),
    "a last chunk past the picks": (HELD, 80, "most", 2),
}


@pytest.mark.parametrize("case", CASES)
def test_chunked_layer_against_the_dense_loop(interpret, case):
    held, tokens, here, trips = CASES[case]
    layer = _layer(held)
    params, x, cot = _inputs(layer, tokens)
    if here is not None:
        rng = np.random.default_rng(1)
        here = {"a few": rng.integers(0, 2, tokens),
                "most": rng.integers(3, 5, tokens),
                "all": np.full(tokens, K), "none": np.zeros(tokens, int)
                }[here]
        here = _picks(tokens, here)
    capacity = layer.capacity(tokens)
    first, count = layer.experts_held

    def loss(f):
        return lambda params, x: jnp.sum(f(params, x) * cot)

    with jax.default_matmul_precision("highest"):
        y, routing = layer.apply(params, x, here)
        got = jax.jit(jax.value_and_grad(
            loss(lambda p, x: layer.apply(p, x, here)[0]),
            argnums=(0, 1)))(params, x)
        want = jax.jit(jax.value_and_grad(
            loss(lambda p, x: plain(layer, p, x, here)),
            argnums=(0, 1)))(params, x)
        want_y = plain(layer, params, x, here)
    rows = routing.counts[first:first + count]
    took = int(dispatch_chunks(rows, capacity))
    assert took == -(-int(jnp.sum(rows)) // capacity)
    if trips is not None:
        assert took == trips
    assert float(layer.stats(routing).dispatch_chunks) == took
    _close(y, want_y)
    assert abs(float(got[0]) - float(want[0])) <= 2e-4 * abs(float(want[0]))
    _close(got[1], want[1])
    if trips == 0:
        # the routed experts add nothing and learn nothing
        _close(y, layer.shared.apply(params["shared"], x))
        assert all(float(jnp.max(jnp.abs(g))) == 0.0
                   for g in jax.tree.leaves(got[1][0]["experts"]))


@pytest.mark.parametrize("here, trips", [("none", 1), ("a few", 1),
                                         ("all", 4)])
def test_first_chunk_always_walks_a_chunk_whatever_the_counts(
        interpret, here, trips):
    """The same layer with ``first_chunk_always``: one trip where no pick
    landed (it adds exact zeros and every expert's gradient is zero), the
    trips the rows take elsewhere, value and gradients those of the walk
    by the count alone."""
    by_count = _layer()
    always = DroplessMoE(HIDDEN, EXPERTS, K, FF, FF, scale=2.5,
                         experts_held=HELD, init_std=0.1,
                         first_chunk_always=True)
    params, x, cot = _inputs(by_count, TOKENS)
    rng = np.random.default_rng(1)
    picks = _picks(TOKENS, {"a few": rng.integers(0, 2, TOKENS),
                            "all": np.full(TOKENS, K),
                            "none": np.zeros(TOKENS, int)}[here])

    def run(layer):
        return jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(layer.apply(p, x, picks)[0] * cot),
            argnums=(0, 1)))(params, x)

    with jax.default_matmul_precision("highest"):
        got, want = run(always), run(by_count)
        routing = always.apply(params, x, picks)[1]
    assert float(always.stats(routing).dispatch_chunks) == trips
    assert float(by_count.stats(routing).dispatch_chunks) == (
        0 if here == "none" else trips)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if here == "none":
        assert all(float(jnp.max(jnp.abs(g))) == 0.0
                   for g in jax.tree.leaves(got[1][0]["experts"]))


@pytest.mark.parametrize("room, here, trips", [
    (1.0, "most", 4), (1.5, "most", 2), (3.0, "most", 2),
    (2.0, "a few", 1), (4.0, "all", 1)])
def test_headroom_takes_fewer_trips_to_the_same_result(interpret, room,
                                                       here, trips):
    """Row buffers of ``room`` even shares: the same rows in fewer, larger
    chunks, the result and every gradient the dense loop's."""
    layer = DroplessMoE(HIDDEN, EXPERTS, K, FF, FF, scale=2.5,
                        experts_held=HELD, init_std=0.1,
                        first_chunk_always=True, dispatch_headroom=room)
    params, x, cot = _inputs(layer, TOKENS)
    rng = np.random.default_rng(1)
    picks = _picks(TOKENS, {"a few": rng.integers(0, 2, TOKENS),
                            "most": rng.integers(3, 5, TOKENS),
                            "all": np.full(TOKENS, K)}[here])
    # the even share is 256 rows, one tile of the grouped product
    assert layer.capacity(TOKENS) == 256 * min(math.ceil(room), K)

    def loss(f):
        return lambda params, x: jnp.sum(f(params, x) * cot)

    with jax.default_matmul_precision("highest"):
        _, routing = layer.apply(params, x, picks)
        got = jax.jit(jax.value_and_grad(
            loss(lambda p, x: layer.apply(p, x, picks)[0]),
            argnums=(0, 1)))(params, x)
        want = jax.jit(jax.value_and_grad(
            loss(lambda p, x: plain(layer, p, x, picks)),
            argnums=(0, 1)))(params, x)
    assert float(layer.stats(routing).dispatch_chunks) == trips
    assert abs(float(got[0]) - float(want[0])) <= 2e-4 * abs(float(want[0]))
    _close(got[1], want[1])


def test_headroom_is_at_least_one_share():
    with pytest.raises(ValueError, match="dispatch_headroom"):
        DroplessMoE(HIDDEN, EXPERTS, K, FF, dispatch_headroom=0.5)


def test_capacity_is_the_held_experts_even_share_in_whole_tiles():
    # the benchmark's cell: 32 of 256 held, 8 picks of 16,384 tokens
    assert dispatch_capacity(16384, 8, 32, 256) == 16384
    assert dispatch_capacity(16384, 8, 256, 256) == 131072
    # rounded up to the grouped product's tile, never past the picks
    assert dispatch_capacity(1000, 8, 32, 256) == 1024
    assert dispatch_capacity(80, 4, 8, 16) == 256
    assert dispatch_capacity(40, 4, 8, 16) == 160
    # with head-room: that many even shares, still in whole tiles
    assert dispatch_capacity(16384, 8, 16, 128, 2.0) == 32768
    assert dispatch_capacity(1000, 8, 32, 256, 1.5) == 1536
    assert dispatch_capacity(16384, 8, 16, 128, 16.0) == 131072
    assert int(dispatch_chunks(jnp.asarray([0, 0]), 256)) == 0
    assert int(dispatch_chunks(jnp.asarray([200, 56]), 256)) == 1
    assert int(dispatch_chunks(jnp.asarray([200, 57]), 256)) == 2


# -- what the layer lowers to ------------------------------------------------ #

PRODUCTS = ("ragged_dot_general", "pallas_call")


def _inlined(jaxpr, names):
    """The equations of ``jaxpr`` in order as (primitive, operands,
    results), calls (jit, custom_vjp_call, ...) replaced by their bodies;
    ``names`` maps a body's variables to the caller's."""
    def name(v):
        while isinstance(v, Var) and v in names:
            v = names[v]
        return v

    for eqn in jaxpr.eqns:
        subs = sub_jaxprs(eqn)
        inner = subs[0].jaxpr if len(subs) == 1 else None
        if (inner is not None and subs[0].kind == "call"
                and len(inner.invars) == len(eqn.invars)
                and len(inner.outvars) == len(eqn.outvars)):
            names.update(zip(inner.invars, map(name, eqn.invars)))
            yield from _inlined(inner, names)
            names.update(zip(eqn.outvars, map(name, inner.outvars)))
        else:
            yield (eqn.primitive.name,
                   [v for v in map(name, eqn.invars) if isinstance(v, Var)],
                   list(eqn.outvars))


def _held_at_once(body, rows, widths):
    """The most bytes of row buffers ([rows or rows + 1, a width]) one
    trip of ``body`` holds at once, in the order it is written.  A buffer
    is what a product or a gather reads or writes; what lies between is
    element-wise, fuses into its reader and is made again from the buffer
    it came from, so a buffer is held from where it is made to the last
    read of it or of anything element-wise made from it."""
    eqns = list(_inlined(body, {}))

    def buffer(v):
        shape = getattr(v.aval, "shape", ())
        return (len(shape) == 2 and shape[0] in (rows, rows + 1)
                and shape[1] in widths)

    kept = set()
    for prim, operands, results in eqns:
        if prim in PRODUCTS:
            kept.update(v for v in operands + results if buffer(v))
        elif prim in ("gather", "pad"):
            kept.update(v for v in results if buffer(v))
    assert kept
    made, last, source = {}, {}, {}
    for at, (prim, operands, results) in enumerate(eqns):
        read = set()
        for v in operands:
            read |= {v} if v in kept else source.get(v, set())
        for v in read:
            last[v] = at
        for v in results:
            if v in kept:
                made[v] = at
            elif prim not in PRODUCTS and prim != "gather":
                source[v] = read
    return max(sum(aval_bytes(v) for v in kept
                   if made.get(v, 0) <= at <= last.get(v, 0))
               for at in range(len(eqns)))


def test_no_worst_case_rows_and_the_working_set_counts_what_is_held():
    """4 of 16 experts held, 2 picks a token: a chunk is an eighth of the
    picks.  Nothing in the layer's value and gradient has a row for every
    pick at the model's or the experts' width, and ``working_set_bytes``
    is what a trip of the backward walk holds at once plus the float32
    sum it adds into."""
    hidden, ff, k, tokens = 512, 128, 2, 1024
    layer = DroplessMoE(hidden, EXPERTS, k, ff, ff, experts_held=HELD)
    capacity = layer.capacity(tokens)
    assert capacity == 512 and tokens * k == 2048
    closed = jax.make_jaxpr(jax.value_and_grad(
        lambda p, x: jnp.sum(layer.apply(p, x)[0]), argnums=(0, 1)))(
        jax.eval_shape(layer.init_params, jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((tokens, hidden), jnp.float32))
    widths = (hidden, ff, 2 * ff)
    wide = [v.aval.shape for c in iter_eqns(closed.jaxpr)
            for v in c.eqn.outvars
            if getattr(v.aval, "shape", ())[:1] == (tokens * k,)
            and v.aval.shape[-1] in widths]
    assert not wide
    walks = [c.eqn for c in iter_eqns(closed.jaxpr)
             if c.eqn.primitive.name == "while" and any(
                 i.eqn.primitive.name in PRODUCTS
                 for i in iter_eqns(c.eqn.params["body_jaxpr"].jaxpr))]
    assert len(walks) == 2                      # forward and backward
    held = max(_held_at_once(w.params["body_jaxpr"].jaxpr, capacity, widths)
               for w in walks)
    assert held == capacity * 4 * 3 * (hidden + ff)
    counted = layer.working_set_bytes(tokens, 4)
    shown = held + tokens * hidden * 4
    assert abs(counted - shown) <= 0.1 * shown
