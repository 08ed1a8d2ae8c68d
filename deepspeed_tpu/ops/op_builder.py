"""Op registry + native JIT build layer.

TPU-native analogue of the reference ``op_builder/`` system (``OpBuilder`` ABC
builder.py:116, JIT compile ``OpBuilder.jit_load`` builder.py:544, reflection
enumeration all_ops.py:22-32). Device ops are Pallas kernels (or fused XLA
subgraphs); *host* ops — async file I/O for the NVMe tier, CPU optimizers for
offload — are C++ shared libraries under ``csrc/`` JIT-compiled with g++ on
first load (the reference uses ninja+pybind11; this image has neither, so we
drive g++ directly and bind via ctypes).
"""

import hashlib
import os
import platform
import shutil
import subprocess
import threading

from deepspeed_tpu.utils.logging import logger

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_CSRC_DIR = os.path.join(_REPO_ROOT, "csrc")
_BUILD_DIR = os.environ.get(
    "DSTPU_BUILD_DIR", os.path.join(_REPO_ROOT, "build", "dstpu_ops")
)
_BUILD_LOCK = threading.Lock()


class OpBuilder:
    """Base class: a named, lazily-loaded op implementation."""

    NAME = "base_op"

    def __init__(self):
        self._loaded = None

    def is_compatible(self, verbose=False):
        return True

    def load(self, verbose=True):
        if self._loaded is None:
            self._loaded = self._build()
            if verbose:
                logger.info(f"Loaded TPU op: {self.NAME}")
        return self._loaded

    def _build(self):
        raise NotImplementedError


class PallasOpBuilder(OpBuilder):
    """An op backed by a Pallas TPU kernel with a jnp reference fallback on CPU."""

    def _build(self):
        raise NotImplementedError


def jit_native(name, sources, extra_flags=(), verbose=False):
    """Compile ``csrc/`` sources into ``build/dstpu_ops/<name>.so`` and return
    the .so path, rebuilding only when a source is newer than the artifact
    (reference ``OpBuilder.jit_load`` builder.py:544, minus ninja).

    Raises ``RuntimeError`` when there is no toolchain or the compile fails:
    a path that needs the op must not quietly take the pure-Python route (the
    ``DSTPU_DISABLE_NATIVE_<NAME>`` switch is how to ask for that route).
    """
    srcs = [s if os.path.isabs(s) else os.path.join(_CSRC_DIR, s) for s in sources]

    def artifact(flags):
        # -march=native bakes in this host's ISA: artifacts must be per-host
        # when the build dir may be shared (repo on NFS in multi-host jobs).
        host = [platform.machine()]
        if any("native" in f for f in flags):
            host.append(platform.node())
        tag = hashlib.sha1("|".join(srcs + list(flags) + host).encode()).hexdigest()[:8]
        return os.path.join(_BUILD_DIR, f"{name}-{tag}.so")

    def fresh(path):
        return os.path.exists(path) and all(
            os.path.getmtime(path) >= os.path.getmtime(s) for s in srcs
        )

    def compile_to(out, flags):
        # Compile to a process-unique temp path and os.replace into place:
        # concurrent processes (pytest-xdist, multi-host launches) never see a
        # half-written .so, and the loser of the race just overwrites with an
        # identical artifact.
        tmp = f"{out}.tmp.{os.getpid()}"
        cmd = (["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread"]
               + list(flags) + srcs + ["-o", tmp])
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
        except (OSError, subprocess.TimeoutExpired) as e:  # no g++ / hung compile
            raise RuntimeError(f"native build of {name} unavailable: {e}") from e
        if proc.returncode != 0:
            logger.warning(f"native build of {name} with {list(flags)} failed:\n"
                           f"{proc.stderr[-2000:]}")
            return None
        os.replace(tmp, out)
        return out

    base_flags = ()
    with _BUILD_LOCK:
        out_full = artifact(extra_flags)
        out_base = artifact(base_flags)
        # Degraded (no-extra-flags) builds are cached under their OWN tag, and
        # the full-flags compile is ALWAYS retried first when its artifact is
        # missing/stale — a cached degraded build never pins a capable host to
        # the slow path.
        if fresh(out_full):
            return out_full
        os.makedirs(_BUILD_DIR, exist_ok=True)
        out = compile_to(out_full, extra_flags)
        if out is None and extra_flags:
            out = out_base if fresh(out_base) else compile_to(out_base, base_flags)
        if out is None:
            raise RuntimeError(
                f"native build of {name} failed with and without {list(extra_flags)} "
                "(compiler output logged above)")
        if verbose:
            logger.info(f"built native op {name} -> {out}")
        return out


class NativeOpBuilder(OpBuilder):
    """An op backed by a g++-compiled C++ shared library bound via ctypes.

    Subclasses set ``SOURCES`` (paths relative to ``csrc/``) and implement
    ``_bind(lib)`` to declare ctypes signatures on the loaded CDLL.
    ``cls.lib()`` is the shared once-per-process accessor (honoring the
    ``DSTPU_DISABLE_NATIVE_<NAME>`` kill switch); modules use it instead of
    hand-rolled globals.
    """

    SOURCES = ()
    EXTRA_FLAGS = ("-fopenmp", "-march=native", "-funroll-loops")
    _lib_cache = {}  # per-class: NAME -> CDLL or None

    def is_compatible(self, verbose=False):
        # Cheap capability probe (reference ds_report semantics): do NOT
        # compile as a side effect — a toolchain or an already-built artifact
        # means the op can load. A cached None means the kill switch: report
        # incompatible, not available.
        if self.NAME in self._lib_cache:
            return self._lib_cache[self.NAME] is not None
        return shutil.which("g++") is not None

    @classmethod
    def lib(cls):
        """Load (building if needed) and cache the CDLL. None only when the
        ``DSTPU_DISABLE_NATIVE_<NAME>`` switch asked for the Python route."""
        if cls.NAME not in NativeOpBuilder._lib_cache:
            if os.environ.get(f"DSTPU_DISABLE_NATIVE_{cls.NAME.upper()}") == "1":
                NativeOpBuilder._lib_cache[cls.NAME] = None
            else:
                NativeOpBuilder._lib_cache[cls.NAME] = cls()._build()
        return NativeOpBuilder._lib_cache[cls.NAME]

    def _build(self):
        import ctypes

        lib = ctypes.CDLL(jit_native(self.NAME, self.SOURCES, self.EXTRA_FLAGS))
        self._bind(lib)
        return lib

    def _bind(self, lib):
        raise NotImplementedError


# Populated by the @register_op decorators in deepspeed_tpu/ops/__init__.py.
ALL_OPS = {}


def register_op(builder_cls):
    ALL_OPS[builder_cls.NAME] = builder_cls
    return builder_cls


def build_all_ops(verbose=True):
    """AOT-build every native op now (reference ``DS_BUILD_OPS=1`` setup.py
    path — pre-compiling instead of JIT on first use). Pallas ops have no
    build step; native ones compile their .so. Returns {name: ok}."""
    import deepspeed_tpu.ops  # noqa: F401 — populate the registry

    results = {}
    for name, cls in sorted(ALL_OPS.items()):
        builder = cls()
        try:
            if isinstance(builder, NativeOpBuilder):
                results[name] = cls.lib() is not None
            else:
                builder.load(verbose=False)
                results[name] = True
        except Exception as e:
            logger.warning(f"build_all_ops: {name} failed: {e!r}")
            results[name] = False
        if verbose:
            logger.info(f"build_all_ops: {name} -> {'ok' if results[name] else 'FAILED'}")
    return results


if __name__ == "__main__":  # python -m deepspeed_tpu.ops.op_builder
    import sys

    ok = build_all_ops()
    sys.exit(0 if all(ok.values()) else 1)
