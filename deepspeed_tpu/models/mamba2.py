"""The Mamba-2 mixer that models/granite_hybrid.py and
models/nemotron_h.py both run (Dao & Gu 2024), around ops/ssd_scan.py:
H heads of P channels (the inner width is H x P, whatever an ``expand``
key says), N states, G groups of B and C that H / G heads each read.

    [z, xBC] = u W_in, dt = u W_dt   (one published ``in_proj`` [hidden,
        2 H P + 2 G N + H], kept as two leaves so that the H step
        columns leave their product in float32)
    [x, B, C] = silu(conv(xBC) + b)  depthwise, causal, ``d_conv`` taps
        (ops/causal_conv.py: read where the projection wrote it, x, B
        and C written H P, G N and G N wide where the scan reads them)
    dt = softplus(dt + dt_bias), A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g], y_t = S_t C_t[g]
        + D x_t                      a head, g its group (ops/ssd_scan.py)
    y = RMSNorm_G(y * silu(z)) * gain   the gate BEFORE the norm, the
        mean square over each group's H P / G channels, a group a
        static slice of lanes reduced on its own (never an axis: that
        reshape is a relayout on the TPU), forward and backward
    out = y W_out

Inside scope ``ssm`` the mixer names its parts (profiling/scope_map.py
PARTS): ``ssm_in``, ``ssm_conv``, ``ssm_scan``, ``ssm_gate``,
``ssm_out``.
"""

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..ops import causal_conv as conv
from ..ops import ssd_scan as ssd

# the op's, importable from here (and from models/granite_hybrid.py)
causal_conv = conv.causal_conv


def gated_rms_norm(y, z, gain, eps, groups=1):
    """``RMSNorm(y * silu(z)) * gain``, the mean square taken over each of
    the ``groups`` equal parts of the last dimension (one: over all of
    it), float32 inside, in y's dtype."""
    if groups > 1:
        return _grouped_gated_norm(y, z, gain, eps, groups)
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g * gain.astype(jnp.float32)).astype(y.dtype)


def _group_slices(width, groups):
    if width % groups:
        raise ValueError(
            f"{width} channels do not divide into {groups} groups")
    return [slice(k, k + width // groups)
            for k in range(0, width, width // groups)]


def _gated(y, z):
    """(y silu(z), silu(z), sigmoid(z)) in float32."""
    z = z.astype(jnp.float32)
    sig = jax.nn.sigmoid(z)
    return y.astype(jnp.float32) * (z * sig), z * sig, sig


def _rstd(g, eps):
    return jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)


# The grouped case never gives the group an axis of its own: on the TPU
# the second-minor dimension of [.., groups, width] is the group, so that
# reshape is a relayout and everything after it a float32 array of the
# activations' size (768 MiB of them at [2, 8192, 4096] in 8 groups).  A
# group is a static slice of lanes instead, reduced on its own and written
# where it belongs, forward and backward; nothing is saved but the op's
# inputs (as ops/normalize.py's LayerNorm and ops/causal_conv.py do).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_gated_norm(y, z, gain, eps, groups):
    return _grouped_fwd(y, z, gain, eps, groups)[0]


def _grouped_fwd(y, z, gain, eps, groups):
    outs = []
    for s in _group_slices(y.shape[-1], groups):
        g, _, _ = _gated(y[..., s], z[..., s])
        outs.append((g * _rstd(g, eps) * gain[s].astype(jnp.float32)
                     ).astype(y.dtype))
    return jnp.concatenate(outs, axis=-1), (y, z, gain)


def _grouped_bwd(eps, groups, res, dout):
    """With g = y silu(z), r = rsqrt(mean_G(g^2) + eps), out = g r gain:
    dg = r (gain dout) - g r^3 mean_G(g gain dout), dy = dg silu(z),
    dz = dg y silu'(z), d gain = sum over the rows of dout g r."""
    y, z, gain = res
    dys, dzs, dgains = [], [], []
    for s in _group_slices(y.shape[-1], groups):
        g, act, sig = _gated(y[..., s], z[..., s])
        r = _rstd(g, eps)
        d = dout[..., s].astype(jnp.float32)
        wd = d * gain[s].astype(jnp.float32)
        dg = r * wd - g * (r * r * r * jnp.mean(g * wd, axis=-1,
                                                keepdims=True))
        dys.append((dg * act).astype(y.dtype))
        dzs.append((dg * y[..., s].astype(jnp.float32)
                    * (sig + act * (1 - sig))).astype(z.dtype))
        dgains.append(jnp.sum((d * g * r).reshape(-1, d.shape[-1]), axis=0))
    return (jnp.concatenate(dys, axis=-1), jnp.concatenate(dzs, axis=-1),
            jnp.concatenate(dgains).astype(gain.dtype))


_grouped_gated_norm.defvjp(_grouped_fwd, _grouped_bwd)


@dataclass(frozen=True)
class Mamba2Mixer:
    """The mixer's sizes, its parameters and its function."""
    hidden_size: int
    n_heads: int
    d_head: int
    d_state: int
    n_groups: int = 1
    d_conv: int = 4
    chunk_size: int = ssd.CHUNK
    eps: float = 1e-5

    def __post_init__(self):
        if self.n_heads % self.n_groups:
            raise ValueError(
                f"{self.n_heads} heads do not divide into {self.n_groups} "
                "groups of B and C")

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.d_head

    @property
    def conv_dim(self) -> int:
        """Channels the conv runs over: x, B and C."""
        return self.d_inner + 2 * self.n_groups * self.d_state

    def init_params(self, keys, normal, out_normal=None):
        """The mixer's leaves, drawn in a fixed order from the iterator
        ``keys``: ``normal(shape)`` draws a matrix (from ``keys`` too),
        ``out_normal`` the output projection where it is drawn another
        way.  Conv taps uniform in +- 1/sqrt(taps) (torch's Conv1d
        default), bias 0; ``A_log = log(uniform(1, 16))`` a head; ``D``
        1; ``dt_bias`` the inverse softplus of steps log-uniform in
        [1e-3, 0.1]; the gated norm's gain 1."""
        heads, di = self.n_heads, self.d_inner
        dt = jnp.exp(jax.random.uniform(next(keys), (heads,), jnp.float32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return {
            "in_w": normal((self.hidden_size, di + self.conv_dim)),  # z, xBC
            "dt_w": normal((self.hidden_size, heads)),
            "conv_w": jax.random.uniform(
                next(keys), (self.conv_dim, self.d_conv),
                jnp.float32, -1.0, 1.0) / math.sqrt(self.d_conv),
            "conv_b": jnp.zeros((self.conv_dim,), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (heads,), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((heads,), jnp.float32),
            "norm_w": jnp.ones((di,), jnp.float32),
            "out_w": (out_normal or normal)((di, self.hidden_size))}

    def scan_form(self) -> str:
        """``kernel`` where ops/ssd_scan.py's Pallas kernels take these
        shapes on this backend, else ``xla``."""
        return "kernel" if ssd.uses_kernels(
            self.n_heads, self.d_head, self.d_state, self.chunk_size,
            self.n_groups) else "xla"

    def conv_parts(self):
        """Widths of x, B and C along the conv's channels."""
        bc = self.n_groups * self.d_state
        return self.d_inner, bc, bc

    def conv_form(self, seq) -> str:
        """``kernel`` where ops/causal_conv.py's Pallas kernels take the
        conv of ``seq`` positions on this backend, else ``xla``."""
        return "kernel" if conv.uses_kernels(
            seq, self.conv_dim, self.d_conv, self.d_inner,
            self.conv_parts()) else "xla"

    def entry_state_bytes(self, batch, seq) -> int:
        """Bytes of chunk-entry states one mixer's scan saves."""
        return ssd.entry_state_bytes(batch, seq, self.n_heads, self.d_head,
                                     self.d_state, self.chunk_size)

    def apply(self, p, u):
        """u [B, S, hidden] -> [B, S, hidden]."""
        batch, seq, _ = u.shape
        heads, groups, n = self.n_heads, self.n_groups, self.d_state
        di, f32 = self.d_inner, jnp.float32
        with jax.named_scope("ssm"):
            with jax.named_scope("ssm_in"):
                zxbc = u @ p["in_w"]
                z = zxbc[..., :di]
                dt = jnp.dot(u, p["dt_w"], preferred_element_type=f32)
            with jax.named_scope("ssm_conv"):
                x, b, c = causal_conv(zxbc, p["conv_w"], p["conv_b"],
                                      first=di, split=self.conv_parts())
            with jax.named_scope("ssm_scan"):
                y = ssd.ssd_scan(
                    x.reshape(batch, seq, heads, self.d_head),
                    jax.nn.softplus(dt + p["dt_bias"].astype(f32)),
                    -jnp.exp(p["A_log"].astype(f32)),
                    b.reshape(batch, seq, groups, n),
                    c.reshape(batch, seq, groups, n), p["D"].astype(f32),
                    chunk=self.chunk_size).reshape(batch, seq, di)
            with jax.named_scope("ssm_gate"):
                y = gated_rms_norm(y, z, p["norm_w"], self.eps, groups)
            with jax.named_scope("ssm_out"):
                return y @ p["out_w"]
