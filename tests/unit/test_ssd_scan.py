"""ops/ssd_scan.py: the chunked matrix form of Mamba-2's scan, both forms
(the plain XLA one and the Pallas kernels through the interpreter),
against the recurrence walked position by position: values and all six
gradients, decays near 0 and near 1, a sequence that is no multiple of
the chunk, what is refused at trace time, the bytes of the saved
chunk-entry states, and the kernels compiled ahead of time for the v5e
at the benchmark cell's shapes where the TPU's compiler can be described
(a compile is not a run).  The topology is described inside a fixture,
never while a module is imported."""

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis.jaxpr_walk import as_jaxpr, iter_eqns, sub_jaxprs
from deepspeed_tpu.ops import ssd_scan as ssd
from deepspeed_tpu.ops.dispatch import set_pallas_interpret

NAMES = ("x", "dt", "a", "b", "c", "d")


def recurrence(x, dt, a, b, c, d):
    """The module's first two equations, one position at a time; head h
    of H reads group h // (H / G) of b and c [batch, S, G, N]."""
    per = x.shape[2] // b.shape[2]

    def row(x, dt, b, c):
        def step(state, at):
            x_t, dt_t, b_t, c_t = at
            b_t, c_t = (jnp.repeat(t, per, axis=0) for t in (b_t, c_t))
            state = (jnp.exp(dt_t * a)[:, None, None] * state
                     + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return state, (jnp.sum(state * c_t[:, None, :], axis=-1)
                           + d[:, None] * x_t)
        zero = jnp.zeros(x.shape[1:] + b.shape[-1:], jnp.float32)
        return jax.lax.scan(step, zero, (x, dt, b, c))[1]
    return jax.vmap(row)(x, dt, b, c)


def operands(batch, seq, heads, dim, states, seed=0, dt_shift=-2.0,
             dt_scale=1.0, groups=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    args = (jax.random.normal(k[0], (batch, seq, heads, dim)),
            dt_scale * jax.nn.softplus(
                jax.random.normal(k[1], (batch, seq, heads)) + dt_shift),
            -jnp.exp(jax.random.uniform(k[2], (heads,), minval=0.0,
                                        maxval=2.7)),
            0.3 * jax.random.normal(k[3], (batch, seq, groups, states)),
            0.3 * jax.random.normal(k[4], (batch, seq, groups, states)),
            jax.random.normal(k[5], (heads,)))
    return args, jax.random.normal(k[6], (batch, seq, heads, dim))


def compare(args, weight, chunk, tol):
    """The op's y and six gradients against the recurrence's, each by
    its norm."""
    y, want = ssd.ssd_scan(*args, chunk=chunk), recurrence(*args)
    assert y.shape == want.shape and y.dtype == args[0].dtype
    assert bool(jnp.isfinite(y).all())
    assert float(jnp.linalg.norm(y - want) / jnp.linalg.norm(want)) < tol
    ours = jax.grad(lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk=chunk)
                                       * weight), argnums=range(6))(*args)
    theirs = jax.grad(lambda *a: jnp.sum(recurrence(*a) * weight),
                      argnums=range(6))(*args)
    for name, g, r in zip(NAMES, ours, theirs):
        assert g.shape == r.shape and bool(jnp.isfinite(g).all()), name
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err < tol, (name, err)


@pytest.fixture
def interpreter():
    set_pallas_interpret(True)
    yield
    set_pallas_interpret(False)


@pytest.mark.parametrize("seq", [96, 80])
def test_the_xla_form_is_the_recurrence(seq):
    """Whole chunks, and a sequence padded to them (80 = 2.5 chunks)."""
    args, weight = operands(2, seq, 4, 8, 16)
    assert not ssd.uses_kernels(4, 8, 16, 32)
    compare(args, weight, 32, 1e-4)


def test_the_kernels_are_the_recurrence(interpreter):
    """Two chunks of 128, one block of eight heads of 64: the carried
    state, the entry states and their cotangents all in play.  bf16
    operands into float32 sums: a few parts in a thousand."""
    args, weight = operands(1, 256, 8, 64, 128)
    assert ssd.uses_kernels(8, 64, 128, 128)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        ssd.ssd_scan(*a, chunk=128) * weight), argnums=range(6)))(*args)
    kernels = sorted({ctx.eqn.params["name"] for ctx in iter_eqns(jaxpr)
                      if ctx.eqn.primitive.name == "pallas_call"})
    assert kernels == ["ssd_bwd", "ssd_fwd"]
    assert all(k.startswith("ssd_") for k in kernels)
    compare(args, weight, 128, 2e-2)


def test_the_kernels_take_a_padded_sequence_and_two_blocks(interpreter):
    """200 positions in chunks of 128 (the second chunk 72 real
    positions), sixteen heads (two blocks of eight: C B^T and its
    cotangent are shared across the blocks), two rows."""
    args, weight = operands(2, 200, 16, 64, 128, seed=3)
    compare(args, weight, 128, 2e-2)


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("case", ["near_one", "near_zero"])
def test_decays_near_one_and_near_zero(form, case, request):
    """Steps of 1e-4 (a decay of 0.999 and more a position: the state
    carries across every chunk) and steps of 30 (a decay under 1e-13: a
    position forgets everything before it, and exp(s_i - s_j) underflows
    for every pair but the diagonal); nothing overflows, nothing is
    NaN."""
    shift, scale = {"near_one": (-9.0, 1.0), "near_zero": (3.0, 10.0)}[case]
    if form == "kernel":
        request.getfixturevalue("interpreter")
        args, weight = operands(1, 256, 8, 64, 128, seed=5, dt_shift=shift,
                                dt_scale=scale)
        compare(args, weight, 128, 2e-2)
    else:
        args, weight = operands(1, 64, 4, 8, 16, seed=5, dt_shift=shift,
                                dt_scale=scale)
        compare(args, weight, 16, 2e-4)


def both_forms(args, weight, chunk):
    """(kernels', twin's, recurrence's) six gradients, the interpreter on
    for the first alone."""
    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                        argnums=range(6))(*args)
    set_pallas_interpret(True)
    kernel = grads(lambda *a: ssd.ssd_scan(*a, chunk=chunk))
    set_pallas_interpret(False)
    twin = grads(lambda *a: ssd.ssd_scan(*a, chunk=chunk))
    return kernel, twin, grads(recurrence)


@pytest.mark.parametrize("groups,heads", [(1, 8), (8, 64)])
@pytest.mark.parametrize("case", ["padded", "near_one", "near_zero"])
def test_the_kernel_finishes_its_cotangents(case, groups, heads, interpreter):
    """dx, the sums over a head's channels and <dG, G> come out of
    ``ssd_bwd`` itself; the twin makes them in XLA by the module's
    formulas.  Two routes to the same six cotangents, each against the
    recurrence too, on a chunk and a bit (the second chunk padded) and on
    two whole chunks at decays near 1 and near 0.  dt, a and d are the
    ones whose route changed: they are held by name, at half the
    kernels' limit."""
    seq, shift, scale = {"padded": (168, -2.0, 1.0),
                         "near_one": (256, -9.0, 1.0),
                         "near_zero": (256, 3.0, 10.0)}[case]
    args, weight = operands(1, seq, heads, 64, 128, seed=5,
                            dt_shift=shift, dt_scale=scale, groups=groups)
    assert ssd.uses_kernels(heads, 64, 128, 128, groups)
    kernel, twin, want = both_forms(args, weight, 128)
    seen = {}
    for name, k, t, r in zip(NAMES, kernel, twin, want):
        size = float(jnp.linalg.norm(r))
        assert bool(jnp.isfinite(k).all()), name
        assert float(jnp.linalg.norm(t - r)) / size < 2e-4, name
        seen[name] = (float(jnp.linalg.norm(k - t)) / size,
                      float(jnp.linalg.norm(k - r)) / size)
        assert max(seen[name]) < 2e-2, (name, seen[name])
    for name in ("dt", "a", "d"):
        assert max(seen[name]) < 1e-2, (name, seen[name])


@pytest.mark.parametrize("groups", [1, 2])
def test_what_ssd_bwd_returns_is_the_twins_by_name(groups, interpreter):
    """``_pallas_bwd``'s eight results against ``_xla_bwd_groups``' and
    the formulas ``_scan_bwd`` puts after it: the finished dx, dB, dC,
    da's first term WITH exp(s_Q) <dG_c, G_c>, the summands of its second
    and third, x . dxb a head and position, and dD whole."""
    chunk, heads = 128, 16
    (x, dt, a, b, c, d), dy = operands(2, 2 * chunk, heads, 64, 128,
                                       seed=4, groups=groups)
    bf16, f32 = jnp.bfloat16, jnp.float32
    # what the kernels see: bf16 x, B, C and dy
    x, b, c, dy = (t.astype(bf16).astype(f32) for t in (x, b, c, dy))
    s = ssd._running(dt, a, chunk)
    cut = [ssd._chunked(t, chunk) for t in (x, dt, s, b, c)]
    entries = jax.vmap(ssd._xla_fwd_groups)(*cut)[1]
    got = ssd._pallas_bwd(x, dt, s, b, c, entries, dy, d, chunk=chunk,
                          interpret=True)
    dxb, d_b, d_c, dg, first, second, third = jax.vmap(
        ssd._xla_bwd_groups)(*cut, entries, ssd._chunked(dy, chunk))
    dxb = dxb.reshape(x.shape)
    through = (jnp.exp(cut[2][:, :, -1])
               * jnp.einsum("bchpn,bchpn->bch", dg, entries))
    want = (dt[..., None] * dxb + d[:, None] * dy, d_b.reshape(b.shape),
            d_c.reshape(c.shape),
            (first + through[:, :, None]).reshape(dt.shape),
            second.reshape(dt.shape), third.reshape(dt.shape),
            jnp.sum(dxb * x, axis=-1), jnp.sum(dy * x, axis=(0, 1, 3)))
    assert got[0].dtype == bf16 and all(t.dtype == f32 for t in got[1:])
    for name, g, w in zip(("dx", "dB", "dC", "first + through", "second",
                           "third", "x . dxb", "dD"), got, want):
        assert g.shape == w.shape, name
        err = float(jnp.linalg.norm(g.astype(f32) - w) / jnp.linalg.norm(w))
        assert err < (1e-5 if name == "dD" else 1e-2), (name, err)


def leaf_eqns_outside_kernels(jaxpr):
    """Every equation that holds no other, the bodies of the Pallas calls
    left out; the calls themselves are yielded."""
    for eqn in as_jaxpr(jaxpr).eqns:
        inner = () if eqn.primitive.name == "pallas_call" else \
            sub_jaxprs(eqn)
        if inner:
            for sub in inner:
                yield from leaf_eqns_outside_kernels(sub.jaxpr)
        else:
            yield eqn


@pytest.mark.parametrize("groups,chunk,states", [(1, 256, 128),
                                                 (8, 128, 256)])
def test_nothing_of_x_in_float32_nor_of_the_entries_is_made_outside(
        groups, chunk, states, interpreter):
    """The kernel path's backward, by its jaxpr at the model's dtypes
    (bf16 x, B, C and dy; a sequence padded to its chunks; a state of
    another size than the chunk, so that no two sizes meet): outside the
    two Pallas calls no float32 array of x's size ([batch, S, H, P],
    however shaped) is produced, and nothing of the saved entries' size
    ([batch, S / Q, H P, N]) but their own reshapes on the way out of
    ``ssd_fwd`` and into ``ssd_bwd``; ``ssd_bwd`` returns dx, dB, dC, four
    rows a head and dD's summands a chunk and channel, and no state's
    cotangent."""
    batch, seq, heads, dim = 2, 3 * chunk - 56, 64, 64
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = [jnp.zeros(shape, dtype) for shape, dtype in (
        ((batch, seq, heads, dim), bf16), ((batch, seq, heads), f32),
        ((heads,), f32), ((batch, seq, groups, states), bf16),
        ((batch, seq, groups, states), bf16), ((heads,), f32))]
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(
        lambda *b: ssd.ssd_scan(*b, chunk=chunk), *a)[1](a[0]))(*args)
    padded, n_chunks = 3 * chunk, 3
    x_size = batch * padded * heads * dim
    entries_size = batch * n_chunks * heads * dim * states
    assert entries_size not in (x_size, batch * seq * heads * dim)
    kernels, entry_reshapes = {}, 0
    for eqn in leaf_eqns_outside_kernels(jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            kernels[eqn.params["name"]] = [v.aval for v in eqn.outvars]
            continue
        for v in eqn.outvars:
            size = getattr(v.aval, "size", 0)
            if v.aval.dtype == f32:
                assert size not in (x_size, batch * seq * heads * dim), eqn
            if size == entries_size:
                assert name == "reshape", eqn
                entry_reshapes += 1
    assert entry_reshapes <= 2
    assert sorted(kernels) == ["ssd_bwd", "ssd_fwd"]
    assert [a.size for a in kernels["ssd_fwd"]] == [x_size, entries_size]
    blocks = heads // ssd.SSD_HEADS
    assert [(a.shape, a.dtype) for a in kernels["ssd_bwd"]] == [
        ((batch, padded, heads * dim), bf16),
        ((batch, padded, groups * states), f32),
        ((batch, padded, groups * states), f32),
        ((batch, blocks, 4 * ssd.SSD_HEADS, padded), f32),
        ((batch, n_chunks, 1, heads * dim), f32)]


def test_what_is_refused_is_refused_with_a_message():
    (x, dt, a, b, c, d), _ = operands(1, 32, 4, 8, 16)
    three = jnp.concatenate([b, b, b], axis=2)
    with pytest.raises(ValueError, match="3 groups"):
        ssd.ssd_scan(x, dt, a, three, three, d, chunk=16)
    with pytest.raises(ValueError, match="must be"):
        ssd.ssd_scan(x.reshape(1, 32, 32), dt, a, b, c, d)
    with pytest.raises(ValueError, match="must be"):
        ssd.ssd_scan(x, dt[:, :16], a, b, c, d)
    # shapes the kernels are not written for take the XLA form, on any
    # backend: heads of 32, six heads, 64 states, a chunk of 96
    assert ssd.kernels_take(64, 64, 128, 256)
    for shape in ((64, 32, 128, 256), (6, 64, 128, 256), (64, 64, 64, 256),
                  (64, 64, 128, 96)):
        assert not ssd.kernels_take(*shape), shape
    # a block of eight heads lies inside one group of B and C, or the
    # XLA form runs: 64 heads in 8 groups are a block each, in 16 half one
    assert ssd.kernels_take(64, 64, 128, 128, groups=8)
    assert ssd.kernels_take(64, 64, 128, 128, groups=4)
    assert not ssd.kernels_take(64, 64, 128, 128, groups=16)
    assert not ssd.kernels_take(64, 64, 128, 128, groups=3)


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_what_the_forward_saves_is_the_chunk_entry_states(form, request):
    """At the benchmark cell's shapes, by shapes alone: sixteen entry
    states a row, 33,554,432 B, and nothing of a position's state's size
    nor a decay matrix for every head and chunk anywhere in the
    program."""
    if form == "kernel":
        request.getfixturevalue("interpreter")
    batch, seq, heads, dim, states, chunk = 1, 4096, 64, 64, 128, 256
    assert ssd.entry_state_bytes(batch, seq, heads, dim, states,
                                 chunk) == 33_554_432
    bf16, f32 = jnp.bfloat16, jnp.float32
    shapes = (jax.ShapeDtypeStruct((batch, seq, heads, dim), bf16),
              jax.ShapeDtypeStruct((batch, seq, heads), f32),
              jax.ShapeDtypeStruct((heads,), f32),
              jax.ShapeDtypeStruct((batch, seq, 1, states), bf16),
              jax.ShapeDtypeStruct((batch, seq, 1, states), bf16),
              jax.ShapeDtypeStruct((heads,), f32))
    y, saved = jax.eval_shape(
        lambda *a: ssd._scan_fwd(*a, chunk), *shapes)
    assert y.shape == shapes[0].shape and y.dtype == bf16
    entries = saved[-1]
    assert entries.shape == (batch, seq // chunk, heads, dim, states)
    assert entries.dtype == f32
    assert entries.size * 4 == 33_554_432
    # the other residuals are the operands themselves
    assert [s.shape for s in saved[:-1]] == [s.shape for s in shapes]
    if form == "kernel":
        jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(
            lambda *b: ssd._scan(*b, chunk), *a)[1](a[0]))(*(
                jnp.zeros(s.shape, s.dtype) for s in shapes))
        largest = max(v.aval.size for ctx in iter_eqns(jaxpr)
                      for v in ctx.eqn.outvars if hasattr(v.aval, "size"))
        # x's own 16.8M elements are the largest array; a decay matrix a
        # head and chunk would be 67M, every position's state 2,147M
        assert largest <= batch * seq * heads * dim, largest


# --------------------------------------------------------------------------- #
# the kernels, compiled for the chip
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache and cannot be
    # read back without a chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_kernels_compile_for_v5e_at_the_cells_shapes(one_chip):
    """[1, 4096, 64, 64], N 128, chunks of 256: the chip's compiler takes
    both calls, each ONE Mosaic call named ``ssd_*``."""
    batch, seq, heads, dim, states, chunk = 1, 4096, 64, 64, 128, 256

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf16, f32 = jnp.bfloat16, jnp.float32
    x = on_chip((batch, seq, heads, dim), bf16)
    per_head = on_chip((batch, seq, heads), f32)
    narrow = on_chip((batch, seq, 1, states), bf16)
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


@pytest.mark.parametrize("cell", ["granite", "nemotron", "phi4"])
def test_the_conv_kernels_compile_for_v5e_at_the_cells_shapes(
        cell, one_chip, monkeypatch):
    """ops/causal_conv.py's two passes before the scan, at `[1, 4096,
    8448]`, `[2, 8192, 10240]` and phi4's `[1, 8192, 10240]`: each ONE
    Mosaic call, nothing of the activations' size beside it."""
    from tests.unit import test_causal_conv as conv
    conv.both_passes_compile_as_one_kernel_each(cell, one_chip, monkeypatch)

