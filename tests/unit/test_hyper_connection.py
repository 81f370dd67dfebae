"""ops/hyper_connection.py against the equations written as loops over
tokens and streams in float32 numpy: the mixes, the sublayer's input and
the written-back streams, and their gradients through every Sinkhorn
round; what the rounds reach; where the clamp bites; one stream as the
plain residual."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import hyper_connection as H

HC = H.HyperConnection(streams=4, sinkhorn_iters=20)
WIDTH, TOKENS = 16, 6


def loop_mixes(x, p, hc):
    """x [n, T, C] float64 numpy -> (pre [T, n], post [T, n],
    res [T, n, n]), a token at a time, from the issue's equations."""
    n, tokens, width = x.shape
    pre, post, res = [], [], []
    for t in range(tokens):
        vec = np.concatenate([x[j, t] for j in range(n)])
        vec = vec / np.sqrt(np.mean(vec ** 2) + hc.norm_eps)
        proj = vec @ p["phi"]
        a, b = p["alpha"], p["b"]
        h_pre = a[0] * proj[:n] + b[:n]
        h_post = a[1] * proj[n:2 * n] + b[n:2 * n]
        h_res = (a[2] * proj[2 * n:] + b[2 * n:]).reshape(n, n)
        m = np.exp(np.clip(h_res, *hc.clamp))
        for _ in range(hc.sinkhorn_iters):
            m = m / (m.sum(axis=1, keepdims=True) + hc.eps)
            m = m / (m.sum(axis=0, keepdims=True) + hc.eps)
        pre.append(1 / (1 + np.exp(-h_pre)))
        post.append(2 / (1 + np.exp(-h_post)))
        res.append(m)
    return np.array(pre), np.array(post), np.array(res)


def loop_sublayer(x, y, p, hc):
    """(u [T, C], X' [n, T, C]) with ``y`` standing for F(u)."""
    pre, post, res = loop_mixes(x, p, hc)
    n, tokens, _ = x.shape
    u = np.array([sum(pre[t, j] * x[j, t] for j in range(n))
                  for t in range(tokens)])
    out = np.array([[sum(res[t, i, j] * x[j, t] for j in range(n))
                     + post[t, i] * y[t] for t in range(tokens)]
                    for i in range(n)])
    return u, out


def drawn(seed, hc=HC, alpha=0.7, spread=1.5):
    """Streams, a sublayer output and parameters away from their
    initial values, so that every term of the equations shows."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n = hc.streams
    x = jax.random.normal(ks[0], (1, n, TOKENS, WIDTH), jnp.float32)
    y = jax.random.normal(ks[1], (1, TOKENS, WIDTH), jnp.float32)
    p = H.init_params(ks[2], hc, WIDTH, std=0.3, alpha=alpha)
    p["b"] = p["b"] + spread * jax.random.normal(ks[3], p["b"].shape)
    return x, y, p


def as_numpy(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_loops(seed):
    x, y, p = drawn(seed)
    u, mixed = H.hc_pre(x, p, HC)
    out = H.hc_post(x, y, mixed)
    pre, post, res = loop_mixes(as_numpy(x)[0], as_numpy(p), HC)
    want_u, want_out = loop_sublayer(as_numpy(x)[0], as_numpy(y)[0],
                                     as_numpy(p), HC)
    np.testing.assert_allclose(mixed.pre[:, 0].T, pre, rtol=2e-5)
    np.testing.assert_allclose(mixed.post[:, 0].T, post, rtol=2e-5)
    np.testing.assert_allclose(mixed.res[:, :, 0].transpose(2, 0, 1), res,
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(u[0], want_u, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out[0], want_out, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("seed", [0, 1])
def test_gradients_match_differences_of_the_loops(seed):
    """The gradient through all 20 rounds: directional derivatives of a
    scalar of (u, X') by central differences of the float64 loops."""
    x, y, p = drawn(seed)
    ks = jax.random.split(jax.random.PRNGKey(100 + seed), 2)
    w_u = jax.random.normal(ks[0], (TOKENS, WIDTH))
    w_x = jax.random.normal(ks[1], (HC.streams, TOKENS, WIDTH))

    def ours(x, y, p):
        u, mixed = H.hc_pre(x, p, HC)
        return (jnp.sum(u[0] * w_u)
                + jnp.sum(H.hc_post(x, y, mixed)[0] * w_x))

    def theirs(x, y, p):
        u, out = loop_sublayer(x[0], y[0], p, HC)
        return np.sum(u * as_numpy(w_u)) + np.sum(out * as_numpy(w_x))

    grads = as_numpy(jax.grad(ours, (0, 1, 2))(x, y, p))
    point = as_numpy((x, y, p))
    rng = np.random.default_rng(seed)
    for _ in range(3):
        way = jax.tree.map(lambda a: rng.standard_normal(a.shape), point)
        step = 1e-5
        up, down = (jax.tree.map(lambda a, d: a + s * d, point, way)
                    for s in (step, -step))
        want = (theirs(*up) - theirs(*down)) / (2 * step)
        got = sum(np.sum(g * d) for g, d in zip(jax.tree.leaves(grads),
                                                jax.tree.leaves(way)))
        assert got == pytest.approx(want, rel=2e-3)


def test_one_round_is_another_function():
    """The rounds matter to value and gradient: a test that passes on
    one round for twenty would pin nothing."""
    x, y, p = drawn(0)
    one = HC._replace(sinkhorn_iters=1)
    res20 = H.mixes(x, p, HC).res
    res1 = H.mixes(x, p, one).res
    assert float(jnp.max(jnp.abs(res20 - res1))) > 1e-2


def test_rows_and_columns_sum_to_one_after_the_rounds():
    """Columns, normalised last, sum to 1 within a few eps after any
    number of rounds; the rows' error falls round by round: from logits
    of spread 1.5 about a common level, under 1e-3 after twenty where
    one round leaves tenths; from the initial parameters under 1e-5.
    (About an identity, entries e^-8 beside e^0 with the same spread, a
    nearly triangular token converges slowly and twenty rounds leave
    1e-2: the stated error is the counter's to report, not a constant.)"""
    x, _, p = drawn(3)
    n = HC.streams
    level = p["b"].at[2 * n:].add(jnp.where(
        jnp.eye(n, dtype=bool), 0.0, 8.0).reshape(-1))
    errors = []
    for iters in (1, 5, 20):
        mixed = H.mixes(x, {**p, "b": level},
                        HC._replace(sinkhorn_iters=iters))
        rows, cols, pre, post = (float(v) for v in H.mix_counters(mixed))
        assert cols <= 8 * HC.eps
        assert 0.0 < pre < 1.0 and 0.0 < post < 2.0
        errors.append(rows)
    assert errors[0] > 0.1 > errors[1] > errors[2]
    assert errors[2] < 1e-3
    slow = float(H.mix_counters(H.mixes(x, p, HC))[0])
    assert errors[2] < slow < 0.03
    start = H.init_params(jax.random.PRNGKey(0), HC, WIDTH)
    rows, cols, pre, post = (float(v) for v in H.mix_counters(
        H.mixes(x, start, HC)))
    assert rows < 1e-5 and cols < 1e-5
    assert pre == pytest.approx(0.25, rel=0.02)
    assert post == pytest.approx(1.0, rel=0.02)


def test_the_clamp_bites_at_thirty():
    """Logits beyond the clamp read as the clamp: 35 and 31 in one row
    give equal weights, and no gradient reaches a clamped entry."""
    hc = HC._replace(streams=2, sinkhorn_iters=20)
    logits = jnp.array([[35.0, 31.0], [31.0, 35.0]])[..., None]
    clamped = H.sinkhorn(logits, hc)[..., 0]
    np.testing.assert_allclose(clamped, 0.5, rtol=1e-5)
    free = H.sinkhorn(logits, hc._replace(clamp=(-100.0, 100.0)))[..., 0]
    assert float(free[0, 0]) > 0.95
    inside = jnp.array([[29.0, 25.0], [25.0, 29.0]])[..., None]
    assert float(H.sinkhorn(inside, hc)[0, 0, 0]) > 0.95
    grad = jax.grad(lambda logit: H.sinkhorn(logit, hc)[0, 0, 0])(
        jnp.array([[35.0, 3.0], [2.0, 1.0]])[..., None])
    assert float(grad[0, 0, 0]) == 0.0 and float(grad[0, 1, 0]) != 0.0
    low = H.sinkhorn(-logits, hc)[..., 0]
    np.testing.assert_allclose(low, 0.5, rtol=1e-5)


def test_one_stream_is_the_plain_residual():
    """n = 1 with the mixes at 1: x + y; and the initial parameters of
    one stream give those mixes."""
    hc = H.HyperConnection(streams=1)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], (2, 1, TOKENS, WIDTH))
    y = jax.random.normal(ks[1], (2, TOKENS, WIDTH))
    ones = H.Mixes(jnp.ones((1, 2, TOKENS)), jnp.ones((1, 2, TOKENS)),
                   jnp.ones((1, 1, 2, TOKENS)))
    np.testing.assert_allclose(H.read(x, ones.pre), x[:, 0])
    np.testing.assert_allclose(H.hc_post(x, y, ones)[:, 0], x[:, 0] + y,
                               rtol=1e-6)
    u, mixed = H.hc_pre(x, H.init_params(ks[2], hc, WIDTH, alpha=0.0), hc)
    np.testing.assert_allclose(u, x[:, 0], rtol=1e-5)
    np.testing.assert_allclose(H.hc_post(x, y, mixed)[:, 0], x[:, 0] + y,
                               rtol=1e-4, atol=1e-5)


def test_initial_mixes_are_the_plain_network_on_equal_streams():
    """H_pre = 1 / n, H_post = 1 and H_res the identity but for
    exp(-8) a pair: n equal streams stay equal, each ``x + y``."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    one = jax.random.normal(ks[0], (1, 1, TOKENS, WIDTH))
    x = jnp.broadcast_to(one, (1, HC.streams, TOKENS, WIDTH))
    y = jax.random.normal(ks[1], (1, TOKENS, WIDTH))
    p = H.init_params(ks[2], HC, WIDTH)
    u, mixed = H.hc_pre(x, p, HC)
    np.testing.assert_allclose(mixed.pre, 0.25, rtol=0.05)
    np.testing.assert_allclose(mixed.post, 1.0, rtol=0.05)
    np.testing.assert_allclose(u, one[:, 0], rtol=0.05, atol=0.05)
    out = H.hc_post(x, y, mixed)
    for j in range(HC.streams):
        np.testing.assert_allclose(out[:, j], one[:, 0] + y, rtol=0.05,
                                   atol=0.05)


def test_bfloat16_streams_mix_in_float32():
    """Carried in bfloat16 the mixes are float32 and equal to those of
    the same (rounded) streams and phi in float32."""
    x, y, p = drawn(5, alpha=0.05)
    xb = x.astype(jnp.bfloat16)
    pb = {**p, "phi": p["phi"].astype(jnp.bfloat16)}
    got = H.mixes(xb, pb, HC)
    want = H.mixes(xb.astype(jnp.float32),
                   {**p, "phi": pb["phi"].astype(jnp.float32)}, HC)
    assert got.res.dtype == jnp.float32
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    out = H.hc_post(xb, y.astype(jnp.bfloat16), got)
    assert out.dtype == jnp.bfloat16 and out.shape == x.shape


def test_the_named_residuals_are_offered():
    from deepspeed_tpu.runtime.activation_checkpointing.checkpointing \
        import offered_residuals
    x, y, p = drawn(0)

    def body(x, p):
        u, mixed = H.hc_pre(x, p, HC)
        return H.hc_post(x, u, mixed)

    offered = offered_residuals(body, x, p)
    assert set(offered) == {H.MIX_NAME, H.INPUT_NAME}
