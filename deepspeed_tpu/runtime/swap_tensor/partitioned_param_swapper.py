"""Parameter NVMe swapper — compute-dtype parameter groups paged through a
pinned host window.

Reference: runtime/swap_tensor/partitioned_param_swapper.py:36
(AsyncPartitionedParameterSwapper) — the ZeRO-Infinity piece that lets the
*parameters themselves* live on NVMe, wired into stage 3 at stage3.py:932 so
a 40B-param model trains on one device (SURVEY.md).

TPU recasting: the unit of paging is a LAYER GROUP (one scanned layer's
param pytree, or the embed/head chains) — the natural streaming granule of
the layer-streaming engine (runtime/zero/infinity.py), playing the role the
reference's per-param ds_tensor handles play.  Groups are flat compute-dtype
files on local SSD; a fixed window of io-aligned host buffers (reference:
pinned buffer pool, utils.py:95) absorbs async reads.

`swap_in(name)` is the in-flight contract the streaming engine carries:
it issues the async read immediately and returns an InflightGroupRead
whose wait() completes ONLY that group's window slot — so the engine can
hold group i+1's read in its loop carry while group i computes (the PR 7
carried-double-buffer discipline, one tier down), and the handle's
issue/wait timestamps make the achieved overlap measurable instead of
assumed.  `prefetch`/`get` remain as the fire-and-forget veneer over the
same machinery.
"""

import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

import jax

from ...utils.logging import log_dist
from .aio_handle import AsyncIOHandle, handle_kwargs
from .utils import aligned_empty


class _Group:
    """Inventory of one paging group: leaf shapes/dtypes and a flat span."""

    def __init__(self, name: str, tree: Any):
        self.name = name
        leaves, self.treedef = jax.tree_util.tree_flatten(tree)
        self.shapes = [tuple(np.shape(leaf)) for leaf in leaves]
        self.dtypes = [np.asarray(leaf).dtype for leaf in leaves]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.nbytes = sum(sz * dt.itemsize
                          for sz, dt in zip(self.sizes, self.dtypes))

    def flatten(self, tree: Any) -> np.ndarray:
        leaves = self.treedef.flatten_up_to(tree)
        out = np.empty(self.nbytes, np.uint8)
        off = 0
        for leaf, shape, dtype, size in zip(leaves, self.shapes, self.dtypes,
                                            self.sizes):
            arr = np.ascontiguousarray(np.asarray(leaf, dtype=dtype))
            nb = size * dtype.itemsize
            out[off:off + nb] = arr.reshape(-1).view(np.uint8)
            off += nb
        return out

    def unflatten(self, buf: np.ndarray) -> Any:
        leaves = []
        off = 0
        for shape, dtype, size in zip(self.shapes, self.dtypes, self.sizes):
            nb = size * dtype.itemsize
            leaves.append(buf[off:off + nb].view(dtype).reshape(shape))
            off += nb
        return self.treedef.unflatten(leaves)


class InflightGroupRead:
    """One issued swap-in.  wait() blocks only on THIS group's window slot
    and returns the host tree; the issue→wait timestamps split the read's
    wall time into `hidden_s` (elapsed before the caller needed it — the
    window the disk had to work under compute) and `exposed_s` (time the
    caller actually blocked — serialized swap-in time)."""

    def __init__(self, swapper: "PartitionedParamSwapper", name: str):
        self.swapper = swapper
        self.name = name
        self.nbytes = swapper.groups[name].nbytes
        self.t_issue = time.perf_counter()
        self.hidden_s: Optional[float] = None
        self.exposed_s: Optional[float] = None
        self._tree = None

    @property
    def done(self) -> bool:
        return self._tree is not None

    def wait(self, copy: bool = True) -> Any:
        if self._tree is None:
            t0 = time.perf_counter()
            self._tree = self.swapper.get(self.name, copy=copy)
            t1 = time.perf_counter()
            self.hidden_s = t0 - self.t_issue
            self.exposed_s = t1 - t0
            st = self.swapper.stats
            st["read_bytes"] += self.nbytes
            st["read_hidden_s"] += self.hidden_s
            st["read_exposed_s"] += self.exposed_s
        return self._tree


class PartitionedParamSwapper:
    """Pages named parameter groups between NVMe files and a host window.

    API (mirroring the reference swapper's swap_in/swap_out lifecycle):
      write(name, tree)      — (over)write a group's file from host values
      swap_in(name) -> h     — issue async read NOW, carry the handle
      get(name) -> tree      — group's params as host arrays (reads if not
                               resident; completes any pending prefetch)
      prefetch(name)         — async read into a window buffer
      release(name)          — drop the group from the window
      resident_groups        — names currently occupying window buffers
    """

    def __init__(self, swap_dir: str, groups: Dict[str, Any],
                 buffer_count: int = 4, aio_config=None,
                 retry_policy=None):
        os.makedirs(swap_dir, exist_ok=True)
        self.swap_dir = swap_dir
        # transient-EIO/ENOSPC retry around the swap I/O submissions
        # (resilience/retry.py); None = fail on first error, as before
        self.retry_policy = retry_policy
        self.groups = {name: _Group(name, tree)
                       for name, tree in groups.items()}
        kw = handle_kwargs(aio_config)
        self.write_handle = AsyncIOHandle(**kw)
        max_bytes = max(g.nbytes for g in self.groups.values())
        self.buffer_count = max(2, int(buffer_count))
        # one read submission context PER WINDOW BUFFER: completing one
        # slot's read must not block on another slot's in-flight prefetch
        # (reference: PipelinedOptimizerSwapper's dual-handle overlap)
        self._read_handles: List[AsyncIOHandle] = [
            AsyncIOHandle(**kw) for _ in range(self.buffer_count)]
        self._buffers: List[np.ndarray] = [
            aligned_empty(max_bytes, np.uint8)
            for _ in range(self.buffer_count)]
        self._free: List[int] = list(range(self.buffer_count))
        self._resident: Dict[str, int] = {}     # name -> buffer idx
        self._pending: Dict[str, int] = {}      # name -> buffer idx (reading)
        self._lru: List[str] = []
        self._inflight_writes: List[np.ndarray] = []
        # cumulative I/O accounting, drained by the engine per step
        # (snapshot_stats); hidden/exposed come from InflightGroupRead
        self.stats: Dict[str, float] = {
            "read_bytes": 0.0, "read_hidden_s": 0.0, "read_exposed_s": 0.0,
            "prefetch_hits": 0.0, "serialized_reads": 0.0,
            "write_bytes": 0.0, "write_wait_s": 0.0}
        # per-write issue→flush windows for the monitor's trace exporter
        # (docs/telemetry.md); drained by drain_write_events, bounded so
        # an unmonitored engine never grows it past one step's writes
        self._write_events: List[Dict[str, float]] = []
        log_dist(
            f"ZeRO-Infinity param swapper: {len(self.groups)} groups, "
            f"window={self.buffer_count} x {max_bytes >> 20}MiB at "
            f"{swap_dir} (aio_backend={self.write_handle.backend_name})",
            ranks=[0])

    # ------------------------------------------------------------------ #
    def _path(self, name: str) -> str:
        return os.path.join(self.swap_dir, f"param_group_{name}.bin")

    def _io(self, fn, what: str):
        """Run one I/O submission under the retry policy (when set).
        Retry is safe here: pread/pwrite submissions are idempotent —
        re-reading a file or re-writing the same buffer converges."""
        if self.retry_policy is None:
            return fn()
        return self.retry_policy.run(fn, what=what)

    @property
    def resident_groups(self) -> List[str]:
        return list(self._resident) + list(self._pending)

    def snapshot_stats(self) -> Dict[str, float]:
        """Return-and-reset the cumulative I/O counters (per-step window
        accounting in the streaming engine)."""
        snap = dict(self.stats)
        for k in self.stats:
            self.stats[k] = 0.0
        return snap

    def _evict_for(self, name: str) -> int:
        if self._free:
            return self._free.pop()
        # evict least-recently-used resident group (never a pending read)
        for cand in list(self._lru):
            if cand in self._resident and cand != name:
                idx = self._resident.pop(cand)
                self._lru.remove(cand)
                return idx
        raise RuntimeError(
            f"param swapper window exhausted ({self.buffer_count} buffers, "
            f"pending={list(self._pending)}) — raise "
            f"offload_param.buffer_count")

    def _complete_pending(self, name: str) -> None:
        """Finish an in-flight read of `name` (slot becomes resident)."""
        idx = self._pending.pop(name)
        self._read_handles[idx].wait()   # only THIS slot's read
        self._resident[name] = idx
        self._lru.append(name)

    # ------------------------------------------------------------------ #
    def write(self, name: str, tree: Any, async_op: bool = False) -> None:
        g = self.groups[name]
        # a pending read of this group streams from the very file the
        # pwrite below will truncate — complete it first or the reader
        # sees a torn mix of old and new bytes (the in-flight-buffer
        # contract of aio_handle.py, enforced rather than assumed)
        if name in self._pending:
            self._complete_pending(name)
        flat = g.flatten(tree)
        if name in self._resident:      # keep the window coherent
            idx = self._resident[name]
            self._buffers[idx][:g.nbytes] = flat
        # async submission only borrows the buffer — pin it until wait()
        # (the reference pins its bounce buffers for the same reason)
        self._inflight_writes.append(flat)
        self._write_events.append({"name": name, "bytes": float(g.nbytes),
                                   "t_issue": time.perf_counter()})
        self._io(lambda: self.write_handle.pwrite(
            flat, self._path(name), async_op=async_op), "swap.pwrite")
        self.stats["write_bytes"] += g.nbytes
        if not async_op:
            self.flush_writes()

    def flush_writes(self) -> None:
        t0 = time.perf_counter()
        self.write_handle.wait()
        t1 = time.perf_counter()
        self.stats["write_wait_s"] += t1 - t0
        self._inflight_writes.clear()
        for ev in self._write_events:
            if "t_done" not in ev:
                ev["t_done"] = t1
                ev["wait_s"] = t1 - t0
        if len(self._write_events) > 512:  # unmonitored engines: bounded
            self._write_events = self._write_events[-512:]

    def drain_write_events(self) -> List[Dict[str, float]]:
        """Return-and-reset completed write windows (pending ones stay)."""
        done = [e for e in self._write_events if "t_done" in e]
        self._write_events = [e for e in self._write_events
                              if "t_done" not in e]
        return done

    def prefetch(self, name: str) -> None:
        if name in self._resident or name in self._pending:
            return
        g = self.groups[name]
        idx = self._evict_for(name)
        buf = self._buffers[idx][:g.nbytes]
        self._io(lambda: self._read_handles[idx].pread(
            buf, self._path(name), async_op=True), "swap.pread")
        self._pending[name] = idx

    def swap_in(self, name: str) -> InflightGroupRead:
        """Issue the group's read NOW and return the carryable handle."""
        self.prefetch(name)
        return InflightGroupRead(self, name)

    def get(self, name: str, copy: bool = True) -> Any:
        """Group params as host arrays.  copy=True (default) detaches the
        result from the window buffer — callers hand these to async
        device uploads, and a subsequent prefetch may overwrite the
        window slot before the upload drains (a releases-too-early
        use-after-free otherwise).  copy=False returns zero-copy views for
        synchronous consumers."""
        g = self.groups[name]
        if name in self._pending:
            self._complete_pending(name)
            self.stats["prefetch_hits"] += 1
        elif name not in self._resident:
            # no read in flight: the caller pays the full disk latency
            # inline — the serialized swap-in the prefetch exists to hide
            self.stats["serialized_reads"] += 1
            idx = self._evict_for(name)
            buf = self._buffers[idx][:g.nbytes]
            self._io(lambda: self._read_handles[idx].pread(
                buf, self._path(name), async_op=False), "swap.pread")
            self._resident[name] = idx
            self._lru.append(name)
        else:
            self._lru.remove(name)
            self._lru.append(name)
        idx = self._resident[name]
        tree = g.unflatten(self._buffers[idx][:g.nbytes])
        if copy:
            tree = jax.tree.map(lambda a: np.array(a, copy=True), tree)
        return tree

    def release(self, name: str) -> None:
        if name in self._pending:
            self._complete_pending(name)
        if name in self._resident:
            self._free.append(self._resident.pop(name))
            if name in self._lru:
                self._lru.remove(name)
