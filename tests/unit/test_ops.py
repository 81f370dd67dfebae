"""Kernel-parity tests — the analog of the reference's
tests/unit/test_cuda_forward.py:333 / test_cuda_backward.py:335 (fused kernels
vs a plain implementation within fp16/fp32 tolerances).

The Pallas kernels run in interpreter mode on the CPU test mesh; the same
kernel code compiles for real TPUs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import (DeepSpeedTransformerConfig,
                               DeepSpeedTransformerLayer, bias_gelu,
                               flash_attention, fused_layer_norm, gelu,
                               layer_norm_reference, mha_reference)
from deepspeed_tpu.ops.flash_attention import flash_attention_pallas


def _qkv(b=2, h=4, s=128, d=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, h, s, d), dtype)
    v = jax.random.normal(ks[2], (b, h, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_pallas_matches_reference(causal):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=64,
                                 block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_interpret_mode_dropout_keeps_the_kernel_contract():
    """The TPU PRNG has no CPU lowering, so interpret mode hashes the
    same two seed values instead (_interpret_random_bits): the keep rate
    matches, the mask is a function of (seed, tile), and the backward
    kernels regenerate the forward's mask — everything the chip's stream
    is relied on for, on another stream (tests/tpu checks the chip's)."""
    from deepspeed_tpu.ops.flash_attention import flash_attention_bwd_pallas
    q, k, _v = _qkv(s=128)
    v = jnp.ones_like(q)

    def run(seed, rate=0.25):
        return flash_attention_pallas(q, k, v, block_q=64, block_k=64,
                                      interpret=True, dropout_rate=rate,
                                      dropout_seed=seed)

    # v = 1: each output is the kept, rescaled probability mass of its row
    out = np.asarray(run(11))
    assert abs(out.mean() - 1.0) < 0.02 and out.std() > 0.01
    np.testing.assert_array_equal(out, np.asarray(run(11)))
    assert not np.allclose(out, np.asarray(run(12)))

    out, lse = flash_attention_pallas(q, k, v, block_q=64, block_k=64,
                                      interpret=True, return_lse=True,
                                      dropout_rate=0.25, dropout_seed=11)
    do = jnp.ones_like(q)
    _, _, dv = flash_attention_bwd_pallas(
        q, k, v, out, lse, do, block_q=64, block_k=64, interpret=True,
        dropout_rate=0.25, dropout_seed=11)
    # dV = dropped(P)^T @ dO and out = dropped(P) @ v: with dO = v = 1 the
    # two sums agree only if both sides drew the same mask
    np.testing.assert_allclose(float(jnp.sum(dv[..., 0])),
                               float(jnp.sum(out[..., 0])), rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_bwd_pallas_matches_reference(causal):
    from deepspeed_tpu.ops.flash_attention import flash_attention_bwd_pallas
    q, k, v = _qkv(s=128)
    do = jax.random.normal(jax.random.PRNGKey(7), q.shape, q.dtype)
    out, lse = flash_attention_pallas(q, k, v, causal=causal, block_q=64,
                                      block_k=64, interpret=True,
                                      return_lse=True)
    dq, dk, dv = flash_attention_bwd_pallas(
        q, k, v, out, lse, do, causal=causal, block_q=64, block_k=64,
        interpret=True)

    def ref_loss(q_, k_, v_):
        r = mha_reference(q_, k_, v_, causal=causal).astype(jnp.float32)
        return jnp.vdot(r, do.astype(jnp.float32))

    rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), rtol=1e-4,
                               atol=1e-4)


def test_flash_attention_public_dispatch_and_grad():
    q, k, v = _qkv(s=64)
    out = flash_attention(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    def loss(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(mha_reference(q_, k_, v_, causal=True) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_attention_bias_path():
    q, k, v = _qkv(s=32)
    bias = jax.random.normal(jax.random.PRNGKey(9), (2, 1, 32, 32))
    out = flash_attention(q, k, v, bias=bias)
    ref = mha_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


def test_fused_layer_norm_grad():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    gamma, beta = jnp.ones((64,)), jnp.zeros((64,))

    g = jax.grad(lambda x_: jnp.sum(fused_layer_norm(x_, gamma, beta) ** 2))(x)
    gr = jax.grad(
        lambda x_: jnp.sum(layer_norm_reference(x_, gamma, beta) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=1e-4,
                               atol=1e-5)


def test_fused_layer_norm_saves_its_inputs_and_no_statistics():
    """The rule's residuals are (x, gamma, beta): the backward recomputes
    the forward from them.  Plain autodiff of the same lines would save
    the row statistics and the normalized rows as well, which is another
    grad program for every model that calls this."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64), jnp.bfloat16)
    gamma, beta = jnp.ones((64,)), jnp.zeros((64,))

    def shapes(fn):
        _, pull = jax.vjp(lambda *a: fn(*a), x, gamma, beta)
        return sorted(tuple(leaf.shape) for leaf in jax.tree.leaves(pull))

    assert shapes(fused_layer_norm) == [(8, 64), (64,), (64,)]
    assert (8, 1) in shapes(layer_norm_reference)   # the statistics


@pytest.mark.parametrize("seq", [200, 8])
def test_dropout_on_a_key_block_of_no_whole_words_takes_xla(seq,
                                                            monkeypatch):
    """The draw packs four key columns a PRNG word, and a key block the
    kernels see is whole lane tiles: any other length, with dropout or
    without, is the XLA path's (no Pallas call in the program)."""
    from deepspeed_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "_interpret", True)
    monkeypatch.setenv("DS_FLASH_MIN_SEQ", "0")
    q = jnp.zeros((1, 2, seq, 32), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: flash_attention(
        x, x, x, causal=True, dropout_rate=0.1, dropout_seed=3))(q)
    assert "pallas_call" not in str(jaxpr)
    # the same call on whole lane tiles does take the kernels
    q = jnp.zeros((1, 2, 256, 32), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: flash_attention(
        x, x, x, causal=True, dropout_rate=0.1, dropout_seed=3))(q)
    assert "flash_fwd" in str(jaxpr)


def test_gelu_matches_tanh_formula():
    x = jnp.linspace(-3, 3, 64)
    expected = 0.5 * x * (1 + jnp.tanh(0.7978845608 * (x + 0.044715 * x ** 3)))
    np.testing.assert_allclose(np.asarray(gelu(x)), np.asarray(expected),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(bias_gelu(x, jnp.zeros_like(x))),
                               np.asarray(expected), rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("pre_ln", [True, False])
def test_transformer_layer_shapes_and_determinism(pre_ln):
    cfg = DeepSpeedTransformerConfig(
        batch_size=2, hidden_size=64, heads=4, num_hidden_layers=2,
        pre_layer_norm=pre_ln, bf16=False, causal=True,
        attn_dropout_ratio=0.0, hidden_dropout_ratio=0.0)
    layer = DeepSpeedTransformerLayer(cfg)
    params = layer.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    out = layer(params, x, deterministic=True)
    assert out.shape == x.shape
    out2 = layer(params, x, deterministic=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2))
    # differentiable end-to-end
    g = jax.grad(lambda p: jnp.sum(layer(p, x, deterministic=True) ** 2))(
        params)
    assert jax.tree.all(jax.tree.map(
        lambda t: bool(jnp.all(jnp.isfinite(t))), g))


def test_transformer_layer_dropout_uses_rng():
    cfg = DeepSpeedTransformerConfig(
        batch_size=2, hidden_size=32, heads=2, num_hidden_layers=1,
        bf16=False, attn_dropout_ratio=0.5, hidden_dropout_ratio=0.5)
    layer = DeepSpeedTransformerLayer(cfg)
    params = layer.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    a = layer(params, x, rng=jax.random.PRNGKey(2))
    b = layer(params, x, rng=jax.random.PRNGKey(3))
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_tp_partition_specs_cover_all_params():
    cfg = DeepSpeedTransformerConfig(batch_size=1, hidden_size=32, heads=2,
                                     num_hidden_layers=1)
    layer = DeepSpeedTransformerLayer(cfg)
    params = layer.init_params(jax.random.PRNGKey(0))
    specs = DeepSpeedTransformerLayer.param_partition_specs()
    assert set(specs) == set(params)


@pytest.mark.parametrize("tp", [2, 4])
def test_transformer_layer_manual_tp_matches_single(tp):
    """The explicit-collective TP mode (tp_axis=, used by the gated 1F1B
    executor) must match the single-device layer bit-for-tolerance:
    forward, input grad, and EVERY param grad — the f/g operator pair
    (tp_fcast/tp_psum, ops/tp_collectives.py) restores full cotangents per device, so no
    post-hoc grad correction exists to hide an error."""
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = DeepSpeedTransformerConfig(
        batch_size=2, hidden_size=32, heads=4, num_hidden_layers=1,
        bf16=False, causal=True,
        attn_dropout_ratio=0.0, hidden_dropout_ratio=0.0)
    layer = DeepSpeedTransformerLayer(cfg)
    params = layer.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))

    def ref_loss(p, x):
        return (layer(p, x, deterministic=True).astype(jnp.float32)
                ** 2).sum()

    ref_y = layer(params, x, deterministic=True)
    ref_gp, ref_gx = jax.grad(ref_loss, argnums=(0, 1))(params, x)

    mesh = Mesh(np.array(jax.devices()[:tp]).reshape(tp), ("model",))
    specs = DeepSpeedTransformerLayer.tp_manual_view_specs()

    def region(p_local, x):
        def loss(p, x):
            y = layer(p, x, deterministic=True, tp_axis="model")
            return (y.astype(jnp.float32) ** 2).sum()

        y = layer(p_local, x, deterministic=True, tp_axis="model")
        gp, gx = jax.grad(loss, argnums=(0, 1))(p_local, x)
        return y, gp, gx

    f = jax.jit(jax.shard_map(
        region, mesh=mesh, in_specs=(specs, P()),
        out_specs=(P(), specs, P()),
        axis_names=frozenset({"model"}), check_vma=False))
    viewed = DeepSpeedTransformerLayer.tp_manual_views(params, cfg.heads)
    y, gp, gx = f(viewed, x)
    gp = DeepSpeedTransformerLayer.tp_manual_unview(gp)

    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(ref_gx),
                               atol=1e-4)
    for key in params:
        np.testing.assert_allclose(
            np.asarray(gp[key]), np.asarray(ref_gp[key]), atol=1e-4,
            err_msg=f"param grad mismatch: {key}")


def test_tp_manual_view_roundtrip():
    """tp_manual_views/unview must be exact inverses on stacked
    [S, k, ...] pipeline leaves (the engine applies the view before the
    shard_map and the unview to the returned grads)."""
    cfg = DeepSpeedTransformerConfig(batch_size=1, hidden_size=32, heads=4,
                                     num_hidden_layers=1)
    layer = DeepSpeedTransformerLayer(cfg)
    single = layer.init_params(jax.random.PRNGKey(0))
    stacked = jax.tree.map(
        lambda leaf: jnp.stack([jnp.stack([leaf, leaf + 1.0])] * 3), single)
    viewed = DeepSpeedTransformerLayer.tp_manual_views(stacked, cfg.heads)
    assert viewed["attn_qkvw"].shape == (3, 2, 32, 4, 3, 8)
    assert viewed["attn_qkvb"].shape == (3, 2, 4, 3, 8)
    back = DeepSpeedTransformerLayer.tp_manual_unview(viewed)
    for key in stacked:
        np.testing.assert_array_equal(np.asarray(back[key]),
                                      np.asarray(stacked[key]))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_bsh_layout_matches_reference(causal):
    """The transpose-free [B, S, heads, d] layout (BlockSpecs index the
    head dim) must be numerically identical to the classic [B, H, S, D]
    path — forward and backward."""
    from deepspeed_tpu.ops.flash_attention import flash_attention_bwd_pallas
    q, k, v = _qkv(s=128)

    def to_bsh(t):
        return t.transpose(0, 2, 1, 3)  # [B,H,S,D] -> [B,S,H,D]

    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention_pallas(
        to_bsh(q), to_bsh(k), to_bsh(v), causal=causal, block_q=64,
        block_k=64, interpret=True, layout="bshd")
    np.testing.assert_allclose(np.asarray(to_bsh(out)), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    do = jax.random.normal(jax.random.PRNGKey(7), q.shape, q.dtype)
    out_b, lse = flash_attention_pallas(
        to_bsh(q), to_bsh(k), to_bsh(v), causal=causal, block_q=64,
        block_k=64, interpret=True, return_lse=True, layout="bshd")
    dq, dk, dv = flash_attention_bwd_pallas(
        to_bsh(q), to_bsh(k), to_bsh(v), out_b, lse, to_bsh(do),
        causal=causal, block_q=64, block_k=64, interpret=True,
        layout="bshd")

    def ref_loss(q_, k_, v_):
        r = mha_reference(q_, k_, v_, causal=causal).astype(jnp.float32)
        return jnp.vdot(r, do.astype(jnp.float32))

    rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(to_bsh(dq)), np.asarray(rq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(to_bsh(dk)), np.asarray(rk),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(to_bsh(dv)), np.asarray(rv),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_bsh_public_fallback_and_grad():
    """flash_attention_bsh on CPU (pallas unusable) falls back to the
    transposed XLA reference and stays differentiable."""
    from deepspeed_tpu.ops.flash_attention import flash_attention_bsh
    q, k, v = _qkv(s=64)

    def to_bsh(t):
        return t.transpose(0, 2, 1, 3)

    out = flash_attention_bsh(to_bsh(q), to_bsh(k), to_bsh(v), causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(to_bsh(out)), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    def loss(q_):
        o = flash_attention_bsh(to_bsh(q_), to_bsh(k), to_bsh(v),
                                causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def ref_l(q_):
        return jnp.sum(mha_reference(q_, k, v,
                                     causal=True).astype(jnp.float32) ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(loss)(q)),
                               np.asarray(jax.grad(ref_l)(q)),
                               rtol=1e-4, atol=1e-4)


def test_transformer_layer_bshd_layout_matches_bhsd():
    """attn_layout='bshd' (transpose-free) must be numerically identical
    to the classic layout at the LAYER level — both routes feed the same
    reference math on CPU and the same kernel pair on TPU."""
    from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerConfig,
                                               DeepSpeedTransformerLayer)

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32), jnp.float32)

    outs = []
    for layout in ("bhsd", "bshd"):
        cfg = DeepSpeedTransformerConfig(
            hidden_size=32, heads=4, attn_dropout_ratio=0.0,
            hidden_dropout_ratio=0.0, bf16=False, causal=True,
            attn_layout=layout)
        layer = DeepSpeedTransformerLayer(cfg)
        params = layer.init_params(jax.random.PRNGKey(1))
        outs.append(np.asarray(layer(params, x, deterministic=True)))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-5)


def test_transformer_layer_bshd_under_tensor_parallel():
    """attn_layout='bshd' with Megatron-split qkv over the model axis:
    the head dim the BlockSpecs index is the SHARDED dim under TP, so
    parity with the bhsd path on a model=2 mesh de-risks the layout flip
    for TP configs."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerConfig,
                                               DeepSpeedTransformerLayer)
    from jax.sharding import NamedSharding

    ds.reset_mesh_context()
    ctx = ds.initialize_mesh(data=-1, model=2)
    try:
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 32),
                              jnp.float32)
        outs = []
        for layout in ("bhsd", "bshd"):
            cfg = DeepSpeedTransformerConfig(
                hidden_size=32, heads=4, attn_dropout_ratio=0.0,
                hidden_dropout_ratio=0.0, bf16=False, causal=True,
                attn_layout=layout)
            layer = DeepSpeedTransformerLayer(cfg)
            params = layer.init_params(jax.random.PRNGKey(1))
            specs = DeepSpeedTransformerLayer.param_partition_specs()
            sharded = {
                k: jax.device_put(v, NamedSharding(ctx.mesh, specs[k]))
                for k, v in params.items()}
            out = jax.jit(lambda p, xx: layer(p, xx, deterministic=True))(
                sharded, x)
            outs.append(np.asarray(out))
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-5)
    finally:
        ds.reset_mesh_context()


def test_flash_attention_dropout_xla_path():
    """CPU (XLA fallback) probability-dropout semantics: deterministic per
    seed, ~rate fraction of attention entries dropped (visible through a
    ones-valued v), exact equality at rate 0, seed requirement."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 2, 64, 16), jnp.float32)
               for kk in ks)
    ones_v = jnp.ones_like(v)

    with pytest.raises(ValueError, match="dropout_seed"):
        flash_attention(q, k, v, dropout_rate=0.1)

    o1 = flash_attention(q, k, ones_v, dropout_rate=0.2, dropout_seed=7)
    o2 = flash_attention(q, k, ones_v, dropout_rate=0.2, dropout_seed=7)
    o3 = flash_attention(q, k, ones_v, dropout_rate=0.2, dropout_seed=8)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert float(jnp.max(jnp.abs(o1 - o3))) > 0.0
    # rows of dropout(P)/keep against ones-v have mean 1 in expectation
    assert abs(float(jnp.mean(o1)) - 1.0) < 0.05

    o0 = flash_attention(q, k, v, dropout_rate=0.0)
    onodrop = flash_attention(q, k, v)
    np.testing.assert_array_equal(np.asarray(o0), np.asarray(onodrop))

    # grads flow and are finite through the dropout path
    g = jax.grad(lambda q_: jnp.sum(
        flash_attention(q_, k, v, dropout_rate=0.2, dropout_seed=7) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()


def test_transformer_layer_training_uses_attention_dropout():
    """In training mode the layer's attention dropout changes the output
    (vs deterministic) and stays reproducible for a fixed rng."""
    from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerConfig,
                                               DeepSpeedTransformerLayer)
    cfg = DeepSpeedTransformerConfig(
        hidden_size=32, heads=4, attn_dropout_ratio=0.3,
        hidden_dropout_ratio=0.0, bf16=False, causal=True)
    layer = DeepSpeedTransformerLayer(cfg)
    params = layer.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 32))
    rng = jax.random.PRNGKey(2)
    det = layer(params, x, deterministic=True)
    tr1 = layer(params, x, rng=rng, deterministic=False)
    tr2 = layer(params, x, rng=rng, deterministic=False)
    np.testing.assert_array_equal(np.asarray(tr1), np.asarray(tr2))
    assert float(jnp.max(jnp.abs(tr1 - det))) > 1e-3


def test_fused_dequant_matmul_interpret_parity():
    """Pallas fused dequant-matmul (interpret) vs the XLA dequant path and
    vs exact fp math, across tiling-friendly and fitted shapes."""
    from deepspeed_tpu.ops.quant import (QuantizedWeight,
                                         fused_dequant_matmul, dequant)
    rng = np.random.RandomState(0)
    for (m, k, n, groups) in [(8, 256, 384, 4), (16, 768, 2304, 8),
                              (128, 128, 128, 1)]:
        x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
        qw = jnp.asarray(rng.randint(-127, 128, (k, n)).astype(np.int8))
        scale = jnp.asarray(
            np.abs(rng.standard_normal((groups, 1))).astype(np.float32))
        w = QuantizedWeight(qw, scale)
        out = fused_dequant_matmul(x, w, interpret=True)
        ref = x @ dequant(w, jnp.float32)
        # blocked-K accumulation reorders fp32 sums vs the single dot
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-3, atol=1e-2)


def test_matmul_maybe_int8_nd_and_plain():
    from deepspeed_tpu.ops.quant import QuantizedWeight, matmul_maybe_int8
    rng = np.random.RandomState(1)
    x3 = jnp.asarray(rng.standard_normal((2, 4, 64)).astype(np.float32))
    qw = jnp.asarray(rng.randint(-127, 128, (64, 96)).astype(np.int8))
    scale = jnp.ones((4, 1), jnp.float32) * 0.5
    w = QuantizedWeight(qw, scale)
    out = matmul_maybe_int8(x3, w)
    assert out.shape == (2, 4, 96)
    ref = jnp.einsum("bsk,kn->bsn", x3, qw.astype(jnp.float32) * 0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    # plain (unquantized) weights unchanged
    wplain = jnp.asarray(rng.standard_normal((64, 96)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(matmul_maybe_int8(x3, wplain)),
                               np.asarray(jnp.einsum("bsk,kn->bsn", x3,
                                                     wplain)), rtol=1e-5)
    # stacked (3-D) quantized weights rejected loudly
    import pytest as _pytest
    wbad = QuantizedWeight(jnp.zeros((2, 64, 96), jnp.int8),
                           jnp.ones((2, 4, 1)))
    with _pytest.raises(ValueError, match="2-D"):
        matmul_maybe_int8(x3, wbad)


def test_fused_dequant_matmul_grad():
    """Differentiation through the fused path (custom VJP: XLA matmul
    backward) matches the plain dequant matmul gradient."""
    from deepspeed_tpu.ops.quant import (QuantizedWeight, _fused_dq,
                                         dequant)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.standard_normal((8, 128)).astype(np.float32))
    qw = jnp.asarray(rng.randint(-127, 128, (128, 256)).astype(np.int8))
    scale = jnp.ones((2, 1), jnp.float32) * 0.1
    w = QuantizedWeight(qw, scale)

    # interpret-mode forward is exercised elsewhere; on CPU the public
    # dispatcher uses the XLA path, so drive the custom-vjp wrapper with
    # the kernel monkeypatched to interpret mode for the fwd
    import deepspeed_tpu.ops.quant as qmod
    import functools as ft
    orig = qmod.fused_dequant_matmul
    qmod.fused_dequant_matmul = ft.partial(orig, interpret=True)
    try:
        g1 = jax.grad(lambda a: jnp.sum(
            _fused_dq(a, w.qweight, w.scale) ** 2))(x)
    finally:
        qmod.fused_dequant_matmul = orig
    g2 = jax.grad(lambda a: jnp.sum((a @ dequant(w, jnp.float32)) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-3, atol=1e-2)


def test_fused_dequant_matmul_scale_grad():
    """The fused path's scale cotangent matches autodiff through the XLA
    dequant path — learned scales get identical gradients on both
    backends (round-3 review finding: it used to be silently zero)."""
    from deepspeed_tpu.ops.quant import (QuantizedWeight, _fused_dq,
                                         dequant)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.standard_normal((8, 128)).astype(np.float32))
    qw = jnp.asarray(rng.randint(-127, 128, (128, 256)).astype(np.int8))
    scale = jnp.asarray(rng.uniform(0.05, 0.2, (4, 1)).astype(np.float32))

    import deepspeed_tpu.ops.quant as qmod
    import functools as ft
    orig = qmod.fused_dequant_matmul
    qmod.fused_dequant_matmul = ft.partial(orig, interpret=True)
    try:
        ds1 = jax.grad(lambda s: jnp.sum(
            _fused_dq(x, qw, s) ** 2))(scale)
    finally:
        qmod.fused_dequant_matmul = orig
    ds2 = jax.grad(lambda s: jnp.sum(
        (x @ dequant(QuantizedWeight(qw, s), jnp.float32)) ** 2))(scale)
    np.testing.assert_allclose(np.asarray(ds1), np.asarray(ds2),
                               rtol=1e-3, atol=1e-2)


def test_dequantize_weight_delegates():
    from deepspeed_tpu.runtime.weight_quantizer import (quantize_weight,
                                                        dequantize_weight)
    rng = np.random.RandomState(4)
    wfull = rng.standard_normal((64, 32)).astype(np.float32)
    qw = quantize_weight(jnp.asarray(wfull), num_groups=4)
    deq = dequantize_weight(qw)
    assert deq.shape == (64, 32)
    np.testing.assert_allclose(np.asarray(deq), wfull, atol=0.05)


def test_dropout_keep_scale_quantization():
    """The in-kernel dropout scale must invert the EXACT quantized keep
    probability the kernel thresholds against: the draw quantizes the
    keep probability to n/256, and using 1/(1-rate) there would bias
    E[attention output] by up to ~0.2%."""
    from deepspeed_tpu.ops.flash_attention import (_keep_scale,
                                                   _quantized_threshold)
    assert _keep_scale(0.1) == 256.0 / round(0.9 * 256)
    assert _keep_scale(0.0) == 1.0   # keep-all: no scaling
    # threshold*scale == 2^width exactly (the shared-definition invariant)
    for rate in (0.05, 0.1, 0.2, 0.5):
        assert _keep_scale(rate) * _quantized_threshold(rate) == 256.0


def test_tp_psum_native_width_knob(monkeypatch):
    """DS_TP_PSUM_NATIVE=1 (the measured native-width mode, VERDICT r4
    weak #5) removes the f32 promotion around sub-f32 manual psums; the
    default keeps it (XLA-CPU AllReducePromotion crash + invariant 4)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.ops.tp_collectives import tp_psum

    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))

    def jaxpr_of(x):
        fn = jax.shard_map(lambda v: tp_psum(v, "model"), mesh=mesh,
                           in_specs=P(), out_specs=P(), check_vma=False)
        return str(jax.make_jaxpr(fn)(x))

    x = jnp.ones((8,), jnp.bfloat16)
    monkeypatch.delenv("DS_TP_PSUM_NATIVE", raising=False)
    assert "f32" in jaxpr_of(x)          # promoted wire by default
    monkeypatch.setenv("DS_TP_PSUM_NATIVE", "1")
    native = jaxpr_of(x)
    assert "f32" not in native           # native bf16 wire
    assert "psum" in native
    # f32 inputs are untouched either way
    monkeypatch.delenv("DS_TP_PSUM_NATIVE", raising=False)
    assert "bf16" not in jaxpr_of(jnp.ones((8,), jnp.float32))


