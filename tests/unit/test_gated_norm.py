"""``models/mamba2.py`` ``gated_rms_norm``: RMSNorm_G(y * silu(z)) * gain
with the mean square over each group's channels.  The grouped case never
makes an axis of the group (a relayout on the TPU); the form that does
is kept HERE, as the reference the values and the three gradients are
held to.  The last two tests compile the grouped function ahead of time
for the v5e at ``nemotron-3-nano-30b-a3b.s8k``'s call and bound XLA's
temporaries, so an edit that brings the relayout back fails on the CPU
machine.  A compile is not a run: nothing here says anything about time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU's library, and
every xdist worker imports every test file.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.mamba2 import gated_rms_norm

EPS = 1e-5
MIB = 2 ** 20


def reference(y, z, gain, eps, groups):
    """The plain formula, the group an axis of its own, float32 inside."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g.reshape(*g.shape[:-1], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(y.shape) * gain.astype(jnp.float32)).astype(y.dtype)


def _operands(groups, width, dtype, seed=0, rows=(2, 24)):
    ky, kz, kg, kd = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (*rows, groups * width)
    return (jax.random.normal(ky, shape, jnp.float32).astype(dtype),
            jax.random.normal(kz, shape, jnp.float32).astype(dtype),
            1.0 + 0.3 * jax.random.normal(kg, shape[-1:], jnp.float32),
            jax.random.normal(kd, shape, jnp.float32).astype(dtype))


def _ulps(got, want):
    """The largest distance in units of ``want``'s spacing at each value."""
    dtype = want.dtype
    got, want = (np.asarray(a.astype(jnp.float32), np.float64)
                 for a in (got, want))
    bits = jnp.finfo(dtype).nmant
    spacing = 2.0 ** (np.floor(np.log2(np.maximum(
        np.abs(want), float(jnp.finfo(dtype).tiny)))) - bits)
    return float(np.max(np.abs(got - want) / spacing))


CASES = [(groups, width, dtype) for groups in (1, 2, 8)
         for width in (128, 512)            # a group of one lane tile, of four
         for dtype in (jnp.bfloat16, jnp.float32)]
IDS = [f"{g}x{w}-{jnp.dtype(d).name}" for g, w, d in CASES]


@pytest.mark.parametrize("groups,width,dtype", CASES, ids=IDS)
def test_values_are_the_plain_formulas(groups, width, dtype):
    y, z, gain, _ = _operands(groups, width, dtype)
    got = jax.jit(gated_rms_norm, static_argnums=(3, 4))(
        y, z, gain, EPS, groups)
    want = reference(y, z, gain, EPS, groups)
    assert got.dtype == dtype and got.shape == y.shape
    if groups == 1:     # the same lines: granite's program does not move
        assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                              np.asarray(want.astype(jnp.float32)))
    else:               # only the order of the sum inside a group differs
        assert _ulps(got, want) <= 1.0


@pytest.mark.parametrize("groups,width,dtype", CASES, ids=IDS)
def test_gradients_are_those_of_the_plain_formula(groups, width, dtype):
    y, z, gain, dout = _operands(groups, width, dtype, seed=1)

    def loss(f):
        return lambda y, z, gain: jnp.sum(
            f(y, z, gain, EPS, groups).astype(jnp.float32)
            * dout.astype(jnp.float32))

    got = jax.jit(jax.grad(loss(gated_rms_norm), argnums=(0, 1, 2)))(
        y, z, gain)
    want = jax.grad(loss(reference), argnums=(0, 1, 2))(y, z, gain)
    # dy and dz are rounded once to the operands' dtype on both sides;
    # d gain is a float32 sum over the rows
    tol = {"bfloat16": 2.0 ** -7, "float32": 2e-5}[jnp.dtype(dtype).name]
    for name, a, b, t in zip(("dy", "dz", "dgain"), got, want,
                             (tol, tol, 2e-5)):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        assert np.max(np.abs(a - b)) <= t * np.max(np.abs(b)), name


@pytest.mark.parametrize("groups", [1, 8])
def test_eps_is_inside_the_root_and_a_dead_group_stays_finite(groups):
    """A group whose every product is zero: r = rsqrt(eps), the output 0,
    and no NaN in any gradient; eps moves the values where it should."""
    y, z, gain, dout = _operands(groups, 128, jnp.float32, seed=2)
    y = y.at[0, 0, :128].set(0.0)           # a dead group through y
    z = z.at[1, 3, -128:].set(0.0)          # and one through silu(0) = 0
    out, vjp = jax.vjp(
        lambda y, z, gain: gated_rms_norm(y, z, gain, EPS, groups),
        y, z, gain)
    grads = vjp(dout)
    assert np.all(np.asarray(out[0, 0, :128]) == 0.0)
    assert np.all(np.asarray(out[1, 3, -128:]) == 0.0)
    for a in (out, *grads):
        assert np.all(np.isfinite(np.asarray(a)))
    # dz of a dead group through y is exactly zero; through z it is
    # dg y silu'(0) = dout gain y / (2 sqrt(eps)) (the group's mean of
    # g gain dout is zero with g)
    assert np.all(np.asarray(grads[1][0, 0, :128]) == 0.0)
    np.testing.assert_allclose(
        np.asarray(grads[1][1, 3, -128:]),
        np.asarray(dout[1, 3, -128:] * gain[-128:] * y[1, 3, -128:]
                   * 0.5 / np.sqrt(EPS)), rtol=1e-5)
    big = gated_rms_norm(y, z, gain, 1.0, groups)
    want = reference(y, z, gain, 1.0, groups)
    np.testing.assert_allclose(np.asarray(big), np.asarray(want),
                               rtol=1e-6, atol=1e-7)
    assert float(jnp.max(jnp.abs(big))) < float(jnp.max(jnp.abs(out)))


def test_groups_must_divide_the_channels():
    y, z, gain, _ = _operands(1, 96, jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        gated_rms_norm(y, z, gain, EPS, 5)


# ---------------------------------------------------------------------- #
# ahead of time for the v5e, at the cell's call
# ---------------------------------------------------------------------- #
CALL = (2, 8192, 4096)      # nemotron-3-nano-30b-a3b.s8k: bf16, 8 groups
GROUPS = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # such a compile is written to the persistent cache and cannot be
    # read back without a chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled(fn, one_chip, cotangent=False):
    """``fn(y, z, gain[, dout])`` compiled for one v5e chip at CALL."""
    big = jax.ShapeDtypeStruct(CALL, jnp.bfloat16, sharding=one_chip)
    gain = jax.ShapeDtypeStruct(CALL[-1:], jnp.float32, sharding=one_chip)
    return jax.jit(fn).lower(big, big, gain, *[big] * cotangent).compile()


def _float32_activations(text):
    """float32 arrays of the activations' size in the optimized program."""
    size = CALL[0] * CALL[1] * CALL[2]
    return {dims for dims in re.findall(r"f32\[([\d,]+)\]", text)
            if np.prod([int(d) for d in dims.split(",")]) == size}


def test_the_grouped_forward_compiles_without_the_relayout(one_chip):
    compiled = _compiled(
        lambda y, z, gain: gated_rms_norm(y, z, gain, EPS, GROUPS),
        one_chip)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 16 * MIB, f"{temp / MIB:.1f} MiB (the reshape: 512)"
    assert not _float32_activations(compiled.as_text())


def test_the_grouped_backward_compiles_without_the_relayout(one_chip):
    def both(y, z, gain, dout):
        out, vjp = jax.vjp(
            lambda y, z, gain: gated_rms_norm(y, z, gain, EPS, GROUPS),
            y, z, gain)
        return out, vjp(dout)

    compiled = _compiled(both, one_chip, cotangent=True)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 192 * MIB, f"{temp / MIB:.1f} MiB (the reshape: 768)"
    assert not _float32_activations(compiled.as_text())
