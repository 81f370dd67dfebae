"""A set-up guard for the rotary kernels (ops/rotary.py), at no chip
time, in tests/unit/test_flash_setup_guard.py's manner: `setup_s` is a
gated metric of the benchmark, and a `pallas_call`'s body is traced in
Python and lowered to Mosaic at every start, in every program that
holds it (`laguna-xs2.s8k`'s grad program holds nine, the parity's
programs theirs).  So the forward and the backward pass, at the cell's
two shapes, must compile for the v5e, each as ONE kernel, and the traced
body must be the loop it is written as: as many equations whatever the
heads of the layer and the positions of a block, and no more than
twice as many for twice the heads a block holds.

The same for ops/latent_layout.py's four passes at `glm47-flash.s8k`'s
shapes (its grad program holds eighteen of each way), in this file so
that one worker describes the topology once for both.

The topology is described inside a module-scoped fixture, never while a
module is imported (see tests/perf/test_aot_kernels.py).
"""

import re

import pytest

KV, DIM = 8, 128
# the QKV products of laguna-xs2.s8k: (batch, positions, query heads,
# half the lanes of a head that turn), a sliding and a full layer
CELL = {"sliding": (2, 8192, 64, 64), "full": (2, 8192, 48, 32)}


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
    import jax
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


@pytest.fixture
def kernels(monkeypatch):
    """ops/rotary.py with the dispatcher saying yes: it asks the default
    backend, which is the CPU here."""
    from deepspeed_tpu.ops import rotary
    monkeypatch.setattr(rotary, "pallas_available", lambda: True)
    return rotary


def _passes(rotary, shape, sharding=None):
    """(the forward pass, the backward pass, their arguments' shapes)."""
    import jax
    import jax.numpy as jnp
    batch, seq, heads, half = shape

    def array(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    qkv = array(batch, seq, (heads + 2 * KV) * DIM)
    tables = [array(seq, DIM, dtype=jnp.float32)] * 2
    by_head = tuple(array(batch, n, seq, DIM) for n in (heads, KV, KV))

    def forward(qkv, cos, sin):
        return rotary.rotate_qkv(qkv, cos, sin, half, heads, KV)

    def backward(qkv, cos, sin, cotangents):
        return jax.vjp(lambda x: forward(x, cos, sin), qkv)[1](cotangents)

    return ((forward, (qkv, *tables)),
            (backward, (qkv, *tables, by_head)))


@pytest.mark.parametrize("kind", CELL)
def test_both_passes_compile_as_one_kernel_each(kind, kernels, one_chip):
    import jax
    for (fn, args), name in zip(_passes(kernels, CELL[kind], one_chip),
                                ("rotary_fwd", "rotary_bwd")):
        lowered = jax.jit(fn).lower(*args)
        calls = re.findall(r'kernel_name = "(\w+)"', lowered.as_text())
        assert calls == [name]
        text = lowered.compile().as_text()  # raises what the chip would
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        # nothing of q's or k's size beside the kernel: no copy, no
        # float32 tensor
        assert not re.search(r"= f32\[2,\d+,8192,|= f32\[2,8192,\d{3,}", text)
        assert " copy(" not in text and " transpose(" not in text


def _equations(jaxpr):
    from deepspeed_tpu.analysis.jaxpr_walk import sub_jaxprs
    return sum(1 + sum(_equations(sub.jaxpr) for sub in sub_jaxprs(eqn))
               for eqn in jaxpr.eqns)


def _bodies(rotary, shape):
    """{kernel name: (equations of its body, grid)} of both passes."""
    import jax
    from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
    found = {}
    for fn, args in _passes(rotary, shape):
        for eqn in (c.eqn for c in iter_eqns(
                jax.make_jaxpr(fn)(*args).jaxpr)):
            if eqn.primitive.name == "pallas_call":
                body = (_equations(eqn.params["jaxpr"]),
                        tuple(eqn.params["grid_mapping"].grid))
                # (jax.vjp traces the forward pass again)
                assert found.setdefault(eqn.params["name"], body) == body
    return found


def test_a_body_is_a_loop_over_positions_and_a_blocks_heads(kernels,
                                                            monkeypatch):
    sliding, full = _bodies(kernels, CELL["sliding"]), _bodies(
        kernels, CELL["full"])
    assert sorted(sliding) == sorted(full) == ["rotary_bwd", "rotary_fwd"]
    # q, k and v in blocks of 8 heads and 512 positions
    assert {grid for _, grid in sliding.values()} == {(2, 16, 10)}
    assert {grid for _, grid in full.values()} == {(2, 16, 8)}
    # the same body a pass but for a full layer's second roll and select
    for name in sliding:
        assert sliding[name][0] < full[name][0] <= 1.3 * sliding[name][0]
    # more heads a layer or positions a sequence: the same body
    more = _bodies(kernels, (2, 16384, 128, 64))
    assert {n: e for n, (e, _) in more.items()} == {
        n: e for n, (e, _) in sliding.items()}
    # fewer positions a block: the same body; half the heads a block:
    # no more than the whole
    monkeypatch.setattr(kernels, "BLOCK_ROWS", 256)
    assert {n: e for n, (e, _) in _bodies(kernels, CELL["sliding"]).items()
            } == {n: e for n, (e, _) in sliding.items()}
    monkeypatch.setattr(kernels, "BLOCK_HEADS", 4)
    for name, (count, _) in _bodies(kernels, CELL["sliding"]).items():
        assert count < sliding[name][0] <= 2 * count
    # and small: three walks (q, k rotated, v copied) of 8 heads
    assert max(e for e, _ in full.values()) <= 600


# ---------------------------------------------------------------------- #
# ops/latent_layout.py: glm47-flash.s8k's passes
# ---------------------------------------------------------------------- #
# (batch, positions, heads, unrotated and rotated lanes of a query or key
# head, lanes of a value head)
LATENT = (2, 8192, 20, 192, 64, 256)


@pytest.fixture
def latent(monkeypatch):
    from deepspeed_tpu.ops import latent_layout
    monkeypatch.setattr(latent_layout, "pallas_available", lambda: True)
    return latent_layout


def _latent_passes(ll, shape, sharding=None):
    """{kernel name: (the pass, its arguments' shapes)}."""
    import jax
    import jax.numpy as jnp
    batch, seq, heads, nope, rope, vdim = shape

    def array(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    flat = (array(batch, seq, heads * (nope + rope)),
            array(batch, seq, heads * (nope - 64)),
            array(batch, seq, heads * 64), array(batch, seq, heads * vdim),
            array(batch, seq, rope))
    tables = (array(seq, DIM, dtype=jnp.float32),) * 2
    by_head = tuple(array(batch, heads, seq, dim)
                    for dim in (nope + rope, nope + rope, vdim))

    def forward(*args):
        return ll.latent_heads(*args, heads)

    def backward(*args):
        *flat, cos, sin, cotangents = args
        return jax.vjp(lambda *x: forward(*x, cos, sin), *flat)[1](
            cotangents)

    def back_backward(a, d_flat):
        return jax.vjp(ll.heads_to_flat, a)[1](d_flat)

    return {
        "latent_heads_fwd": (forward, (*flat, *tables)),
        "latent_heads_bwd": (backward, (*flat, *tables, by_head)),
        "latent_flat_fwd": (ll.heads_to_flat, by_head[2:]),
        "latent_flat_bwd": (back_backward, (by_head[2], flat[3]))}


@pytest.mark.parametrize("name", ["latent_heads_fwd", "latent_heads_bwd",
                                  "latent_flat_fwd", "latent_flat_bwd"])
def test_a_latent_pass_compiles_as_one_kernel(name, latent, one_chip):
    import jax
    assert latent.latent_block(*LATENT[1:2], *LATENT[3:], LATENT[2]) == (
        512, 4)
    fn, args = _latent_passes(latent, LATENT, one_chip)[name]
    lowered = jax.jit(fn).lower(*args)
    assert re.findall(r'kernel_name = "(\w+)"', lowered.as_text()) == [name]
    text = lowered.compile().as_text()      # raises what the chip would
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # nothing of a tensor's size beside the kernel: no transpose, no
    # float32 tensor, no broadcast of the one key to the heads, no copy
    # but the one key's own [2, 8192, 64]
    assert not re.search(r"= f32\[2,\d+,8192,|= f32\[2,8192,\d{3,}", text)
    assert " transpose(" not in text
    assert not re.search(r"\[2,20,8192,\d+\]\S* broadcast\(", text)
    for line in text.splitlines():
        if " copy(" in line:
            assert re.search(r"= bf16\[2,8192,64\]", line), line


def test_the_latent_bodies_are_traced_once_a_process_and_shape(
        latent, monkeypatch):
    """Two layers' calls under two outer traces of value and gradient:
    each kernel's body runs through Python once (the calls sit behind
    cached jits, as ops/rotary.py's), and a body is a loop over
    positions: as many equations whatever the heads of the layer."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.analysis.jaxpr_walk import iter_eqns
    traced = []
    for body in ("_heads_fwd_kernel", "_heads_bwd_kernel", "_flat_kernel"):
        def counting(*refs, _body=getattr(latent, body), _name=body, **kw):
            traced.append((_name, kw.get("backward")))
            return _body(*refs, **kw)
        monkeypatch.setattr(latent, body, counting)
    # a shape no other test of this process has traced
    shape = (3, 1536, 12, 192, 64, 256)
    fn, args = _latent_passes(latent, shape)["latent_heads_fwd"]

    def two_layers(*args):
        total = 0.0
        for scale in (1.0, 2.0):
            q, k, v = fn(args[0] * scale, *args[1:])
            total += jnp.sum(latent.heads_to_flat(q * k * v).astype(
                jnp.float32))
        return total

    sizes = {}
    for _ in range(2):
        jaxpr = jax.make_jaxpr(jax.value_and_grad(two_layers, argnums=(
            0, 1, 2, 3, 4)))(*args)
        for eqn in (c.eqn for c in iter_eqns(jaxpr.jaxpr)):
            if eqn.primitive.name == "pallas_call":
                sizes.setdefault(eqn.params["name"], set()).add(
                    (_equations(eqn.params["jaxpr"]),
                     tuple(eqn.params["grid_mapping"].grid)))
    assert sorted(traced) == [
        ("_flat_kernel", False), ("_flat_kernel", True),
        ("_heads_bwd_kernel", None), ("_heads_fwd_kernel", None)]
    assert {name: grids for name, grids in sizes.items()} == {
        name: {(next(iter(grids))[0], (3, 3, 3))}
        for name, grids in sizes.items()}
    assert sorted(sizes) == ["latent_flat_bwd", "latent_flat_fwd",
                             "latent_heads_bwd", "latent_heads_fwd"]
    # the cell's 20 heads: the same bodies, five blocks of heads
    cell = {}
    for name, (fn, args) in _latent_passes(latent, LATENT).items():
        for eqn in (c.eqn for c in iter_eqns(
                jax.make_jaxpr(fn)(*args).jaxpr)):
            if eqn.primitive.name == "pallas_call":
                cell[eqn.params["name"]] = (
                    _equations(eqn.params["jaxpr"]),
                    tuple(eqn.params["grid_mapping"].grid))
    assert {grid for _, grid in cell.values()} == {(2, 16, 5)}
    assert {n: e for n, (e, _) in cell.items()} == {
        n: next(iter(s))[0] for n, s in sizes.items()}
    # and small: four heads of six tiles a trip
    assert max(e for e, _ in cell.values()) <= 400
