"""ops/ssd_scan.py with several groups of B and C (head h of H reads
group h // (H / G)): the Pallas kernels through the interpreter against
their XLA twin against the recurrence walked position by position, at
chunks of 128 and 256, the output and all six operands' gradients; one
group is the program it was (the kernels' text against its record, the
XLA form against the one-group functions it is made of); and both kernels
compiled ahead of time for the v5e at the shapes of the two benchmark
cells that run them."""

import hashlib
import json
import os
import re

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
from deepspeed_tpu.ops import ssd_scan as ssd
from deepspeed_tpu.ops.dispatch import set_pallas_interpret
from tests.unit.test_ssd_scan import (NAMES, compare, interpreter,  # noqa: F401
                                      one_chip, operands, recurrence)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "ssd_one_group_kernels.json")


def value_and_grads(args, weight, chunk):
    return (ssd.ssd_scan(*args, chunk=chunk), *jax.grad(
        lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk=chunk) * weight),
        argnums=range(6))(*args))


@pytest.mark.parametrize("groups,heads,chunk", [
    (1, 8, 128), (2, 16, 256), (8, 64, 128), (8, 64, 256)])
def test_kernels_twin_and_recurrence_agree_by_group(groups, heads, chunk,
                                                    interpreter):  # noqa: F811
    """A chunk and a bit (the second chunk padded), heads of 64 on 128
    states: the kernels against the recurrence (bf16 operands: parts in a
    thousand) and against the twin, whose float32 sums are the
    recurrence's to 1e-4.  With 8 groups of 64 heads a head block IS a
    group; with 2 of 16 a group is one block; with 1 of 8 the program of
    before."""
    seq = chunk + 40
    args, weight = operands(1, seq, heads, 64, 128, seed=groups,
                            groups=groups)
    assert ssd.uses_kernels(heads, 64, 128, chunk, groups)
    kernel = value_and_grads(args, weight, chunk)
    set_pallas_interpret(False)
    assert not ssd.uses_kernels(heads, 64, 128, chunk, groups)
    twin = value_and_grads(args, weight, chunk)
    want = (recurrence(*args), *jax.grad(
        lambda *a: jnp.sum(recurrence(*a) * weight),
        argnums=range(6))(*args))
    for name, k, t, r in zip(("y",) + NAMES, kernel, twin, want):
        size = float(jnp.linalg.norm(r))
        assert float(jnp.linalg.norm(t - r)) / size < 1e-4, name
        assert float(jnp.linalg.norm(k - t)) / size < 2e-2, name


@pytest.mark.parametrize("groups", [2, 4])
def test_the_twin_takes_groups_a_head_block_would_straddle(groups):
    """Small heads, and groups of two heads: the XLA form on any
    backend."""
    args, weight = operands(2, 80, 8, 8, 16, seed=7, groups=groups)
    assert not ssd.kernels_take(8, 8, 16, 32, groups)
    compare(args, weight, 32, 1e-4)


def test_a_dropped_group_index_is_seen():
    """Every head reading group 0 is another function: the comparison
    that holds the op would not hold it."""
    args, weight = operands(1, 64, 8, 8, 16, seed=2, groups=4)
    x, dt, a, b, c, d = args
    first = (x, dt, a, jnp.repeat(b[:, :, :1], 4, 2),
             jnp.repeat(c[:, :, :1], 4, 2), d)
    y, wrong = ssd.ssd_scan(*args, chunk=32), ssd.ssd_scan(*first, chunk=32)
    assert float(jnp.linalg.norm(y - wrong) / jnp.linalg.norm(y)) > 0.05


def _scans(fn, *args):
    """The scan equations of ``fn`` traced on ``args``, as text."""
    return [str(ctx.eqn) for ctx in iter_eqns(jax.make_jaxpr(fn)(*args))
            if ctx.eqn.primitive.name == "scan"]


def test_one_group_in_the_xla_form_is_the_one_group_functions():
    """G = 1: ``_xla_fwd`` / ``_xla_bwd`` themselves, mapped over the batch
    alone.  That it is the program of before is read off the programs:
    the scans the two forms trace to are equal as text.  Their values
    are then held to float32 rounding (1e-6 of a result's largest), what
    two compilations of one program owe each other: equal bits of two
    runs were the machine's to give, and a loaded worker of the driver's
    run once did not."""
    args, _ = operands(2, 96, 4, 8, 16)
    x, dt, a, b, c, d = args
    y, saved = ssd._scan_fwd(*args, 32)
    s = ssd._running(dt, a, 32)
    chunked = [ssd._chunked(t, 32) for t in (x, dt, s, b[:, :, 0],
                                             c[:, :, 0])]
    grouped = chunked[:3] + [ssd._chunked(t, 32) for t in (b, c)]

    def close(got, want):
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-6 * float(jnp.abs(want).max()))

    assert _scans(jax.vmap(ssd._xla_fwd_groups), *grouped) == _scans(
        jax.vmap(ssd._xla_fwd), *chunked)
    want, entries = jax.vmap(ssd._xla_fwd)(*chunked)
    close(y, want.reshape(x.shape) + d[:, None] * x)
    close(saved[-1], entries)
    dy = ssd._chunked(jnp.ones_like(x), 32)
    assert _scans(jax.vmap(ssd._xla_bwd_groups), *grouped, entries,
                  dy) == _scans(jax.vmap(ssd._xla_bwd), *chunked, entries, dy)
    ours = jax.vmap(ssd._xla_bwd_groups)(*grouped, entries, dy)
    theirs = jax.vmap(ssd._xla_bwd)(*chunked, entries, dy)
    for got, ref in zip(ours, theirs):
        close(got.reshape(ref.shape), ref)


def test_one_groups_kernels_are_the_recorded_ones(interpreter):  # noqa: F811
    """The two Pallas calls of a one-group scan (sixteen heads: two head
    blocks share C B^T and its cotangent), as text, against the record:
    ``ssd_fwd``'s read on the parent of PR 60, ``ssd_bwd``'s on PR 61,
    which changed that kernel (the file says how): same bodies, same
    grid, same blocks."""
    args, weight = operands(1, 256, 16, 64, 128, seed=3)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        ssd.ssd_scan(*a, chunk=128) * weight), argnums=range(6)))(*args)
    with open(GOLDEN) as f:
        want = json.load(f)["kernels"]
    got = {}
    for ctx in iter_eqns(jaxpr):
        if ctx.eqn.primitive.name == "pallas_call":
            text = re.sub(r"0x[0-9a-f]+", "0x", str(ctx.eqn))
            got[ctx.eqn.params["name"]] = {
                "chars": len(text),
                "sha256": hashlib.sha256(text.encode()).hexdigest()}
    assert got == want


@pytest.mark.parametrize("cell", ["granite-4.0-h-micro.s4k",
                                  "nemotron-3-nano-30b-a3b.s8k"])
def test_the_kernels_compile_for_v5e_at_a_cells_shapes(cell, one_chip):  # noqa: F811
    """[1, 4096, 64, 64] in chunks of 256 on one group, [2, 8192, 64, 64]
    in chunks of 128 on eight: the chip's compiler takes both calls, each
    ONE Mosaic call named ``ssd_*``."""
    batch, seq, groups, chunk = {
        "granite-4.0-h-micro.s4k": (1, 4096, 1, 256),
        "nemotron-3-nano-30b-a3b.s8k": (2, 8192, 8, 128)}[cell]
    heads, dim, states = 64, 64, 128

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf16, f32 = jnp.bfloat16, jnp.float32
    x = on_chip((batch, seq, heads, dim), bf16)
    per_head = on_chip((batch, seq, heads), f32)
    narrow = on_chip((batch, seq, groups, states), bf16)
    entries = on_chip((batch, seq // chunk, heads, dim, states), f32)
    d_vec = on_chip((heads,), f32)
    for name, fn, args in (
            ("ssd_fwd", ssd._pallas_fwd.__wrapped__,
             (x, per_head, per_head, narrow, narrow, d_vec)),
            ("ssd_bwd", ssd._pallas_bwd.__wrapped__,
             (x, per_head, per_head, narrow, narrow, entries, x, d_vec))):
        text = jax.jit(lambda *a, fn=fn: fn(
            *a, chunk=chunk, interpret=False)).lower(*args).compile(
            ).as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1, name
        assert f"{name}/pallas_call" in text
