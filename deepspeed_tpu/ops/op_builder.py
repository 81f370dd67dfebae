"""Native-op build system — the analog of the reference's op_builder/
(builder.py:112 OpBuilder, load:344/jit_load:356, ALL_OPS registry
op_builder/__init__.py:18-30).

The reference JIT-compiles CUDA extensions with ninja+nvcc; here the native
pieces are host-side C++ (OpenMP/auto-vectorized) compiled with g++ into
shared libraries loaded via ctypes — no torch extension machinery, no
pybind11 dependency.  Pallas kernels need no native build at all; only the
genuinely-host components (Adam/LAMB for offloaded shards, the async file
I/O engine) live here.

Build artifacts land in <repo>/build/<name>-<srchash>.so; a content hash in
the filename makes staleness detection automatic.
"""

import ctypes
import hashlib
import os
import platform
import subprocess
from typing import Dict, List

from ..utils.logging import logger

def _cpu_identity() -> str:
    """CPU model + ISA flags (what -march=native actually binds to)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("model name", "flags", "Features")):
                    return line.strip()
    except OSError:
        pass
    return platform.processor() or "unknown-cpu"


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CSRC_DIR = os.path.join(_REPO_ROOT, "csrc")
BUILD_DIR = os.environ.get(
    "DS_BUILD_DIR", os.path.join(_REPO_ROOT, "build"))


class OpBuilder:
    """Compile-and-load for one native op (reference: builder.py:112).

    Subclasses define NAME, sources(), and optionally cxx_flags()/ldflags()
    and is_compatible().  load() returns a ctypes.CDLL (cached per-process),
    compiling first if the source hash has no built artifact yet.
    """

    NAME = "base"
    _cache: Dict[str, ctypes.CDLL] = {}

    def sources(self) -> List[str]:
        raise NotImplementedError

    def cxx_flags(self) -> List[str]:
        flags = ["-O3", "-std=c++17", "-fPIC", "-shared", "-fopenmp"]
        if os.environ.get("DS_NATIVE_ARCH", "1") == "1":
            flags.append("-march=native")
        return flags

    def ldflags(self) -> List[str]:
        return []

    def compiler(self) -> str:
        return os.environ.get("CXX", "g++")

    def is_compatible(self) -> bool:
        """Probe the toolchain/OS the way the reference probes libaio/CUDA
        (op_builder/async_io.py:106)."""
        try:
            subprocess.run([self.compiler(), "--version"],
                           capture_output=True, check=True)
            return True
        except (OSError, subprocess.CalledProcessError):
            return False

    # ------------------------------------------------------------------ #
    def hash_inputs(self) -> List[str]:
        """Files whose content keys the build artifact — sources plus any
        private headers (not passed to the compiler, but staleness-
        relevant all the same)."""
        return self.sources()

    def _src_hash(self) -> str:
        h = hashlib.sha256()
        for src in self.hash_inputs():
            with open(src, "rb") as f:
                h.update(f.read())
        h.update(" ".join(self.cxx_flags() + self.ldflags()).encode())
        # -march=native makes the artifact CPU-specific: key it on the CPU
        # identity so a binary built elsewhere is never loaded (SIGILL risk)
        h.update(platform.machine().encode())
        h.update(_cpu_identity().encode())
        return h.hexdigest()[:16]

    def lib_path(self) -> str:
        return os.path.join(BUILD_DIR, f"{self.NAME}-{self._src_hash()}.so")

    def build(self) -> str:
        path = self.lib_path()
        if os.path.exists(path):
            return path
        os.makedirs(BUILD_DIR, exist_ok=True)
        # a temp name of this process's own: concurrent builders (xdist
        # workers on a fresh checkout) must not replace each other's file
        tmp = f"{path}.{os.getpid()}.tmp"
        cmd = ([self.compiler()] + self.cxx_flags() + self.sources() +
               self.ldflags() + ["-o", tmp])
        logger.info(f"building native op {self.NAME}: {' '.join(cmd)}")
        try:
            subprocess.run(cmd, capture_output=True, check=True, text=True)
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"native build of {self.NAME} failed:\n{e.stderr}") from e
        os.replace(tmp, path)  # atomic vs concurrent builders
        return path

    def load(self) -> ctypes.CDLL:
        key = self.lib_path()
        if key not in OpBuilder._cache:
            OpBuilder._cache[key] = ctypes.CDLL(self.build())
        return OpBuilder._cache[key]


class CPUAdamBuilder(OpBuilder):
    """Host Adam/AdamW for offloaded optimizer shards
    (reference: op_builder/cpu_adam.py + csrc/adam/cpu_adam.cpp)."""

    NAME = "cpu_adam"

    def sources(self):
        return [os.path.join(CSRC_DIR, "adam", "host_adam.cpp")]


class AsyncIOBuilder(OpBuilder):
    """Async NVMe file I/O engine (reference: op_builder/async_io.py +
    csrc/aio/).  Two sources: the portable pool engines (threadpool +
    batched-submit preadv/pwritev) and the io_uring ring engine, which is
    compiled everywhere but RUNTIME-probed (ds_uring_probe) — the
    reference probes libaio at build time (async_io.py:106); io_uring
    availability is a kernel/sandbox property, so the probe moves to
    ds_aio_create2 time and aio_handle.py falls back loudly."""

    NAME = "async_io"

    def sources(self):
        return [os.path.join(CSRC_DIR, "aio", "host_aio.cpp"),
                os.path.join(CSRC_DIR, "aio", "uring_aio.cpp")]

    def hash_inputs(self):
        return self.sources() + [os.path.join(CSRC_DIR, "aio",
                                              "aio_backend.h")]

    def ldflags(self):
        return ["-lpthread"]


ALL_OPS: Dict[str, type] = {
    "cpu_adam": CPUAdamBuilder,
    "async_io": AsyncIOBuilder,
}


def op_report() -> Dict[str, Dict[str, object]]:
    """Availability report per op — the `ds_report` data source
    (reference: env_report.py)."""
    report = {}
    for name, cls in ALL_OPS.items():
        builder = cls()
        compatible = builder.is_compatible()
        built = False
        if compatible:
            try:
                built = os.path.exists(builder.lib_path())
            except OSError:
                compatible = False
        report[name] = {"compatible": compatible, "built": built}
    return report
