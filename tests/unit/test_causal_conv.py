"""ops/causal_conv.py on the CPU, the kernels through the interpreter:
the kernel pair against the XLA form AND against a position-by-position
float32 loop, forward and all three gradients, at the two Mamba-2 cells'
channel widths (positions cut), over several position blocks (the halo
crossed both ways); what the kernels refuse and what a backend without
them runs; what stands around the kernels in the jaxpr; what tracing
them costs; and the check that the two calls compile for the v5e at the
cells' shapes, each ONE kernel, which tests/unit/test_ssd_scan.py runs
beside the scan kernels' own."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis.jaxpr_walk import as_jaxpr, iter_eqns, sub_jaxprs
from deepspeed_tpu.ops import causal_conv as cc
from deepspeed_tpu.ops.dispatch import set_pallas_interpret

F32, BF16 = jnp.float32, jnp.bfloat16
# (inner width, B's and C's width) of granite-4.0-h-micro (4,352 channels),
# of nemotron-3-nano-30b-a3b (6,144) and of phi4-mini-flash (5,120 of the
# projection's 10,240, the first half, in one part)
GRANITE, NEMOTRON, PHI4 = (4096, 128), (4096, 1024), (5120, 0)


@pytest.fixture
def interpreter():
    set_pallas_interpret(True)
    yield
    set_pallas_interpret(False)


def layout(widths):
    """(first, split, the projection's width) of a cell's conv."""
    inner, narrow = widths
    if not narrow:
        return 0, (), 2 * inner
    return inner, (inner, narrow, narrow), 2 * inner + 2 * narrow


def operands(batch, seq, widths, taps=4, dtype=BF16, seed=0):
    first, split, width = layout(widths)
    channels = sum(split) or widths[0]
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (batch, seq, width), F32).astype(dtype),
            jax.random.uniform(k[1], (channels, taps), F32, -0.5, 0.5),
            0.3 * jax.random.normal(k[2], (channels,), F32),
            jax.random.normal(k[3], (batch, seq, channels), F32).astype(
                dtype))


def position_by_position(x, w, b, dy):
    """float64 numpy, a position at a time: (y, dx, dw, db) of
    ``sum(y * dy)``; x [B, S, C] the conv's own channels."""
    x, w, b, dy = (np.asarray(t, np.float64) for t in (x, w, b, dy))
    batch, seq, channels = x.shape
    taps = w.shape[1]
    y, dx = np.zeros_like(x), np.zeros_like(x)
    dw, db = np.zeros_like(w), np.zeros_like(b)
    for t in range(seq):
        reads = [(j, t - (taps - 1) + j) for j in range(taps)
                 if t - (taps - 1) + j >= 0]
        pre = b + sum(w[:, j] * x[:, s] for j, s in reads)
        s = 1.0 / (1.0 + np.exp(-pre))
        y[:, t] = pre * s
        d_pre = dy[:, t] * s * (1.0 + pre * (1.0 - s))
        db += d_pre.sum(0)
        for j, src in reads:
            dx[:, src] += w[:, j] * d_pre
            dw[:, j] += (d_pre * x[:, src]).sum(0)
    return y, dx, dw, db


def through(form, args, widths):
    """(y joined, dx over the projection's whole width, dw, db) by the
    op (``kernel``) or by the XLA form on the sliced channels."""
    x, w, b, dy = args
    first, split, _ = layout(widths)
    channels = w.shape[0]

    def conv(x, w, b):
        if form == "xla":
            return cc.xla_causal_conv(x[..., first:first + channels], w, b)
        out = cc.causal_conv(x, w, b, first, split)
        return jnp.concatenate(out, axis=-1) if split else out

    y, vjp = jax.vjp(conv, x, w, b)
    return (y, *vjp(dy))


def rel(a, b):
    a, b = (np.asarray(t, np.float64) for t in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# --------------------------------------------------------------------------- #
# values
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("widths", [GRANITE, NEMOTRON],
                         ids=["granite", "nemotron"])
def test_the_kernels_are_the_xla_form_and_the_loop(widths, batch, interpreter,
                                                   monkeypatch):
    """bf16 as the models hand it, three position blocks of 64 (so the
    rows before a block and after it come from its neighbours, and the
    first block's are the causal zero): y is the XLA form's to a bf16
    value (the CPU contracts multiply-adds, the chip does not: there the
    two read equal bit for bit), and every gradient is as near the
    float64 loop as the XLA form's or nearer."""
    monkeypatch.setattr(cc, "BLOCK_ROWS", 64)
    args = operands(batch, 192, widths)
    first, split, _ = layout(widths)
    channels = args[1].shape[0]
    assert cc.uses_kernels(192, channels, 4, first, split)
    got, want = through("kernel", args, widths), through("xla", args, widths)
    loop = position_by_position(
        args[0][..., first:first + channels], *args[1:])
    y, y_xla = (np.asarray(t[0], np.float32) for t in (got, want))
    assert np.max(np.abs(y - y_xla) / (np.abs(y_xla) + 1e-3)) <= 2.0 ** -7
    assert rel(y, loop[0]) < 3e-3          # bf16's rounding of y
    # nothing flows to the columns the conv does not read
    dx = np.asarray(got[1], np.float32)
    assert dx.shape == args[0].shape
    assert not dx[..., :first].any() and not dx[..., first + channels:].any()
    for name, g, x, ref in zip(("dx", "dw", "db"), got[1:], want[1:],
                               loop[1:]):
        if name == "dx":
            g, x = (t[..., first:first + channels] for t in (g, x))
        assert g.shape == ref.shape and g.dtype == x.dtype, name
        print(name, rel(g, ref), rel(x, ref))
        # dx is rounded once from float32 sums; XLA's own derivative
        # rounds each tap's share to bf16 and sums those
        assert rel(g, ref) <= max(1.05 * rel(x, ref), 1e-6), name
        assert rel(g, ref) < (3e-3 if name == "dx" else 1e-5), name


@pytest.mark.parametrize("taps", [2, 4])
def test_float32_and_fewer_taps(taps, interpreter, monkeypatch):
    """float32 in and out: the kernels ARE the loop to float32's own
    rounding, the first rows' causal zero included; two blocks of 128."""
    monkeypatch.setattr(cc, "BLOCK_ROWS", 128)
    widths = (256, 128)
    args = operands(2, 256, widths, taps=taps, dtype=F32, seed=taps)
    got = through("kernel", args, widths)
    loop = position_by_position(args[0][..., 256:], *args[1:])
    for name, g, ref in zip(("y", "dx", "dw", "db"), got, loop):
        if name == "dx":
            g = g[..., 256:]
        assert g.dtype == F32
        assert rel(g, ref) < 2e-6, (name, rel(g, ref))
    # the first position reads nothing before it: y_0 = silu(w[-1] x_0 + b)
    x, w, b, _ = args
    first_row = jax.nn.silu(x[:, 0, 256:] * w[:, -1] + b)
    np.testing.assert_allclose(got[0][:, 0], first_row, rtol=1e-6, atol=1e-7)


# --------------------------------------------------------------------------- #
# which form runs
# --------------------------------------------------------------------------- #
def kernel_names(fn, *args):
    return sorted(c.eqn.params["name"] for c in iter_eqns(
        jax.make_jaxpr(fn)(*args).jaxpr)
        if c.eqn.primitive.name == "pallas_call")


REFUSED = {
    "channels": ((1, 128, (256, 64)), {},
                 "channels 256 \\+ 256 \\+ 64 \\+ 64 are no whole lane "
                 "tiles of 128"),
    "positions": ((1, 96, (256, 128)), {},
                  "96 positions are no whole blocks of 64"),
    "taps": ((1, 128, (256, 128)), {"taps": 8},
             "8 taps and a bias are more than the 8 rows of a tile"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_the_kernels_refuse_runs_the_xla_form(case, interpreter):
    (batch, seq, widths), more, why = REFUSED[case]
    args = operands(batch, seq, widths, dtype=F32, **more)
    first, split, _ = layout(widths)
    channels, taps = args[1].shape
    assert re.fullmatch(why, cc.refusal(seq, channels, taps, first, split))
    assert not cc.uses_kernels(seq, channels, taps, first, split)
    assert kernel_names(lambda *a: through("kernel", a, widths), *args) == []
    for g, x in zip(through("kernel", args, widths),
                    through("xla", args, widths)):
        np.testing.assert_array_equal(g, x)


def test_no_tpu_and_no_interpreter_is_the_plain_path():
    widths = (256, 128)
    args = operands(1, 128, widths)
    first, split, _ = layout(widths)
    assert cc.refusal(128, 512, 4, first, split) is None
    assert not cc.uses_kernels(128, 512, 4, first, split)
    assert kernel_names(lambda *a: through("kernel", a, widths), *args) == []
    set_pallas_interpret(True)
    try:
        assert kernel_names(lambda *a: through("kernel", a, widths), *args
                            ) == ["causal_conv_bwd", "causal_conv_fwd"]
    finally:
        set_pallas_interpret(False)
    with pytest.raises(ValueError, match="must sum to C"):
        cc.causal_conv(*args[:3], first, (256, 128))
    with pytest.raises(ValueError, match=r"W >= 640 \+ 512"):
        cc.causal_conv(*args[:3], 640, split)


# --------------------------------------------------------------------------- #
# what stands around the kernels
# --------------------------------------------------------------------------- #
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


@pytest.mark.parametrize("widths", [GRANITE, NEMOTRON],
                         ids=["granite", "nemotron"])
def test_no_pad_no_float32_copy_and_no_slice_around_the_kernels(
        widths, interpreter):
    """A mixer's conv by its jaxpr, both ways, at the model's dtypes:
    outside the two Pallas calls nothing pads the positions, nothing of
    the activations' size ([tokens, conv_dim], or a part's) is float32,
    nothing slices or joins them; the one array of that size made
    outside is d xBC set into the projection's width (the transpose of
    reading it in place)."""
    batch, seq = 2, 256
    args = operands(batch, seq, widths)
    first, split, width = layout(widths)
    channels = args[1].shape[0]

    jaxpr = jax.make_jaxpr(lambda x, w, b, *dys: jax.vjp(
        lambda *a: cc.causal_conv(*a, first, split), x, w, b)[1](dys))(
        *args[:3], *(jnp.zeros((batch, seq, n), BF16) for n in split))
    tokens = batch * seq
    big = {tokens * n for n in (channels, *split)}
    kernels, pads = {}, 0
    for eqn in leaf_eqns_outside_kernels(jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            kernels[eqn.params["name"]] = [
                (v.aval.shape, v.aval.dtype) for v in eqn.outvars]
            continue
        for v in eqn.outvars:
            size = getattr(v.aval, "size", 0)
            assert size not in big, eqn
            if size == tokens * width:
                assert name == "pad" and v.aval.dtype == BF16, eqn
                assert eqn.params["padding_config"] == (
                    (0, 0, 0), (0, 0, 0), (first, 0, 0))
                pads += 1
            else:
                assert size <= 8 * channels, eqn   # the taps, the bias
    assert pads == 1
    assert kernels == {
        "causal_conv_fwd": [((batch, seq, n), BF16) for n in split],
        "causal_conv_bwd": [((batch, seq, channels), BF16),
                            ((channels // 128, 8, 128), F32)]}


# --------------------------------------------------------------------------- #
# the kernels, compiled for the chip; what tracing them costs
# --------------------------------------------------------------------------- #
# (batch, positions, widths): granite-4.0-h-micro.s4k,
# nemotron-3-nano-30b-a3b.s8k, phi4-mini-flash.s8k
CELLS = {"granite": (1, 4096, GRANITE), "nemotron": (2, 8192, NEMOTRON),
         "phi4": (1, 8192, PHI4)}


def passes(cell, sharding=None):
    """((the forward pass, its arguments' shapes), (the backward pass,
    its)) at a cell's shapes."""
    batch, seq, widths = CELLS[cell]
    first, split, width = layout(widths)
    channels = sum(split) or widths[0]

    def array(*dims, dtype=BF16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    x, w, b = (array(batch, seq, width), array(channels, 4, dtype=F32),
               array(channels, dtype=F32))
    dys = tuple(array(batch, seq, n) for n in split or (channels,))

    def forward(x, w, b):
        return cc.causal_conv(x, w, b, first, split)

    def backward(x, w, b, dys):
        return jax.vjp(forward, x, w, b)[1](dys if split else dys[0])

    return (forward, (x, w, b)), (backward, (x, w, b, dys))


def both_passes_compile_as_one_kernel_each(cell, one_chip, monkeypatch):
    """For tests/unit/test_ssd_scan.py, which describes the chip once for
    the mixer's kernels (one worker then loads its compiler, not two)."""
    monkeypatch.setattr(cc, "pallas_available", lambda: True)
    batch, seq, widths = CELLS[cell]
    tokens = f"{batch},{seq}"
    for (fn, args), name in zip(passes(cell, one_chip),
                                ("causal_conv_fwd", "causal_conv_bwd")):
        lowered = jax.jit(fn).lower(*args)
        assert re.findall(r'kernel_name = "(\w+)"', lowered.as_text()) == [
            name]
        text = lowered.compile().as_text()  # raises what the chip would
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        # nothing of the activations' size is made beside the kernel but
        # d xBC set into the projection's width: no float32 tensor, no
        # copy, no slice
        made = [(m.group(1), m.group(2)) for m in re.finditer(
            rf"= (\w+)\[{tokens},\d+\]\S* ([\w-]+)\(", text)
            if m.group(2) not in ("parameter", "custom-call",
                                  "get-tuple-element")]
        assert made == ([("bf16", "pad")] if name == "causal_conv_bwd"
                        else []), made


def _equations(jaxpr):
    return sum(1 + sum(_equations(sub.jaxpr) for sub in sub_jaxprs(eqn))
               for eqn in jaxpr.eqns)


def _bodies(cell):
    """{kernel name: (equations of its body, grid)} of both passes."""
    found = {}
    for fn, args in passes(cell):
        for eqn in (c.eqn for c in iter_eqns(
                jax.make_jaxpr(fn)(*args).jaxpr)):
            if eqn.primitive.name == "pallas_call":
                body = (_equations(eqn.params["jaxpr"]),
                        tuple(eqn.params["grid_mapping"].grid))
                # (jax.vjp traces the forward pass again)
                assert found.setdefault(eqn.params["name"], body) == body
    return found


def test_a_body_is_a_loop_over_positions(interpreter, monkeypatch):
    """`setup_s` is a gated metric and a kernel's body is traced in
    Python and lowered at every start: the body is the loop it is
    written as, as many equations whatever the rows of a block and the
    positions of the sequence, three walks (x, B, C) in the hybrids and
    one in phi4; and small."""
    granite, nemotron, phi4 = (_bodies(cell) for cell in sorted(CELLS))
    assert sorted(granite) == ["causal_conv_bwd", "causal_conv_fwd"]
    assert {grid for _, grid in granite.values()} == {(1, 1, 34)}
    assert {grid for _, grid in nemotron.values()} == {(2, 2, 48)}
    assert {grid for _, grid in phi4.values()} == {(1, 2, 40)}
    sizes = {name: count for name, (count, _) in granite.items()}
    assert sizes == {name: count for name, (count, _) in nemotron.items()}
    # (the calls sit behind cached jits, which do not see the constant)
    monkeypatch.setattr(cc, "BLOCK_ROWS", 512)
    for call in (cc._forward, cc._backward):
        call.clear_cache()
    try:
        fewer = _bodies("granite")
    finally:
        monkeypatch.undo()
        for call in (cc._forward, cc._backward):
            call.clear_cache()
    assert {grid for _, grid in fewer.values()} == {(1, 8, 34)}
    assert sizes == {name: count for name, (count, _) in fewer.items()}
    for name, (count, _) in phi4.items():
        # (and the conditions that choose the walk)
        assert count < sizes[name] <= 3 * count + 20
    assert sizes["causal_conv_fwd"] <= 300 and \
        sizes["causal_conv_bwd"] <= 900, sizes
