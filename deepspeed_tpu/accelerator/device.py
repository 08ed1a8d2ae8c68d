"""What this process runs on, asked in one place.

Start-up questions every entry point shares:

  * :func:`on_tpu` — is the JAX backend of this process a TPU? Every Pallas
    wrapper derives ``interpret`` from it, and every "TPU only" switch in the
    package reads it. It is False only on a run that ASKED for the CPU; a
    backend that failed to come up, or one JAX fell back to by itself, raises.
  * :func:`probe_device` — the same question put to a child, for a parent
    that must stay off the chip its children need.
  * :func:`device_peaks` — published peak rates of the device, keyed by
    ``device_kind``. A device that is not in the table is an error, not a
    default.
  * :func:`setup_compile_cache` — where JAX's persistent compilation cache
    lives, so that a second process finds what the first one compiled.
"""

import os
import subprocess
import sys
from typing import NamedTuple, Optional, Tuple

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def cpu_requested() -> bool:
    """Did the user ask for a CPU run (``JAX_PLATFORMS=cpu``, the same value
    through ``jax.config``, or ``DS_ACCELERATOR=cpu``)?"""
    platforms = (jax.config.jax_platforms or "").split(",")
    return platforms[0] == "cpu" or os.environ.get("DS_ACCELERATOR") == "cpu"


def on_tpu() -> bool:
    """True on a TPU backend, False on a requested CPU run, error otherwise.

    Initialises the backend, so a parent process that will start children
    that need the chip must not call it."""
    backend = jax.default_backend()
    if backend == "tpu":
        return True
    if backend == "cpu" and cpu_requested():
        return False
    raise RuntimeError(
        f"JAX came up on {backend!r} but no CPU run was requested: the TPU "
        "could not be initialised (is another process holding the chip?). "
        "Set JAX_PLATFORMS=cpu to run the interpreted CPU paths on purpose."
    )


def probe_device() -> Tuple[str, str]:
    """``(platform, device_kind)`` of the first device a fresh process gets,
    asked of a child that exits before this returns. For a parent that starts
    children which need the chip: a process that has initialised a backend
    holds the chip, and its children then fail or hang."""
    code = "import jax; d = jax.devices()[0]; print(d.platform); print(d.device_kind)"
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, check=True,
    ).stdout.splitlines()
    return out[-2], out[-1]


class Peaks(NamedTuple):
    bf16_flops: float   # FLOP/s
    int8_ops: float     # OP/s
    hbm_bytes_s: float  # bytes/s
    hbm_bytes: int      # device memory
    source: str


# Keyed by the prefix of ``jax.devices()[0].device_kind``.
PEAKS = {
    "TPU v5 lite": Peaks(
        197e12, 393e12, 819e9, 16 << 30,
        'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
        "393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip",
    ),
}


def device_peaks(device_kind: Optional[str] = None) -> Peaks:
    """Peak rates for ``device_kind`` (default: this process's first device).
    Raises ``KeyError`` on a device the table does not know."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    for prefix, peaks in PEAKS.items():
        if device_kind.startswith(prefix):
            return peaks
    raise KeyError(
        f"no published peaks for device kind {device_kind!r}: add it to "
        "deepspeed_tpu.accelerator.device.PEAKS with its source"
    )


def setup_compile_cache() -> str:
    """Give JAX a persistent compilation cache directory that does not move
    between runs, and return it. ``JAX_COMPILATION_CACHE_DIR`` wins: JAX
    reads it by itself and nothing is set here. Otherwise the cache is
    ``<checkout>/.jax_cache`` — a fixed path, because a cache whose directory
    changes is never hit."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
