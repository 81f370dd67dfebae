"""Kernel-backend dispatch: which implementation an op takes, and where a
Pallas call may sit in a sharded program.

Pallas TPU kernels (flash attention) must not lower on CPU
(pallas supports only interpret mode there), and the usual gate —
``jax.default_backend() == "tpu"`` — is wrong in one real scenario: a
process that touched the TPU backend first and then forced
``jax_platforms=cpu`` (the multichip CPU-sim dryrun) still reports "tpu".
This module gives such callers an explicit override, also settable via
``DS_FORCE_XLA_OPS=1``.
"""

import math
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..parallel.mesh import MODEL_AXIS, ZERO_AXES, get_mesh_context

_force_xla = bool(int(os.environ.get("DS_FORCE_XLA_OPS", "0")))
_interpret = False


def force_xla_kernels(on: bool = True) -> None:
    """Route all op dispatchers to their XLA reference paths (no Pallas)."""
    global _force_xla
    _force_xla = on


def pallas_available() -> bool:
    """True when Pallas TPU kernels may be compiled in this process."""
    return not _force_xla and jax.default_backend() == "tpu"


def set_pallas_interpret(on: bool) -> None:
    """Run the flash-attention kernels through the Pallas interpreter
    wherever ``impl="auto"`` would pick them, on any backend.  For the
    CPU tests and ``chip_smoke.py --tiny``, which must walk the same
    dispatch and sharding code as the chip; read at trace time."""
    global _interpret
    _interpret = bool(on)


def pallas_interpret() -> bool:
    return _interpret and not _force_xla


# ---------------------------------------------------------------------- #
# Pallas calls under a device mesh
# ---------------------------------------------------------------------- #
# Mesh axes an attention-shaped operand's batch and head dims lie over.
BATCH_AXES = ZERO_AXES
HEAD_AXES = (MODEL_AXIS,)


def manual_kernel_region(fn, operands, in_dims, out_dims):
    """Call ``fn(shard_index, *operands)`` with EVERY mesh axis manual.

    XLA cannot partition a Mosaic kernel: a pallas_call traced under
    GSPMD, or inside a shard_map that is manual over only some axes (the
    streamed ZeRO-3 region), fails to lower with "Mosaic kernels cannot
    be automatically partitioned" — even when the automatic axes all have
    size 1.  This wraps the call in a ``jax.shard_map`` over the axes
    still automatic at the call site: all of them under plain jit, the
    rest when the caller is already inside a manual region.  With no mesh
    context, a one-device mesh, or nothing left automatic, ``fn`` is
    called as is.

    in_dims: per operand, ``{dim: mesh axes}`` or None (replicated);
    out_dims: the same for the single output.  An axis is dropped from a
    dim — the operand stays replicated over it and every shard along it
    computes the same thing — when the caller's region already holds it,
    or when the axes' combined size does not divide the dim.

    shard_index: int32 scalar, this shard's linear index over the axes
    that do split an operand (0 when none does).  Kernels with their own
    PRNG fold it into the seed so shards draw independent streams; axes
    an operand is replicated over are left out of it on purpose, since
    those shards must agree.
    """
    ctx = get_mesh_context(required=False)
    if ctx is None or ctx.mesh.size == 1:
        return fn(jnp.int32(0), *operands)
    mesh = ctx.mesh
    held = set(jax.sharding.get_abstract_mesh().manual_axes)
    free = [a for a in mesh.axis_names if a not in held]
    if not free:
        return fn(jnp.int32(0), *operands)

    def spec(dims, shape):
        entries = [None] * len(shape)
        for dim, axes in (dims or {}).items():
            axes = tuple(a for a in axes if a in free and mesh.shape[a] > 1)
            if axes and shape[dim] % math.prod(
                    mesh.shape[a] for a in axes) == 0:
                entries[dim] = axes
        return PartitionSpec(*entries)

    in_specs = tuple(spec(d, x.shape) for d, x in zip(in_dims, operands))
    out_spec = spec(out_dims, operands[0].shape)
    # mesh order, each axis once
    used = [a for a in free if any(
        a in (entry or ()) for s in (*in_specs, out_spec) for entry in s)]
    # The shard index rides in as data — an iota laid over the used axes
    # — because lax.axis_index does not lower inside a nested shard_map
    # (shardy rejects re-binding the parent's manual axes).
    sizes = [mesh.shape[a] for a in used]
    index = jnp.arange(math.prod(sizes), dtype=jnp.int32).reshape(sizes)

    def region(index, *local):
        return fn(index.reshape(()), *local)

    return jax.shard_map(
        region, mesh=None if held else mesh,
        in_specs=(PartitionSpec(*used),) + in_specs, out_specs=out_spec,
        axis_names=set(free), check_vma=False)(index, *operands)
