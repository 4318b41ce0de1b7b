"""Device / Place surface.

Reference: `Place`/`CPUPlace`/`CUDAPlace` (`/root/reference/paddle/phi/common/place.h:115`)
and `paddle.set_device` (`python/paddle/device/__init__.py`).  On TPU, device identity
is a `jax.Device`; Places are thin descriptors that resolve to one.
"""
from __future__ import annotations

import functools
import os

import jax


class Place:
    """Base place descriptor (ref place.h:115)."""

    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        devs = [d for d in jax.devices() if d.platform == self.device_type]
        if not devs:
            raise RuntimeError(
                f"{self!r} requested but JAX sees no {self.device_type} "
                f"device (devices: {jax.devices()})")
        return devs[self.device_id % len(devs)]


class CPUPlace(Place):
    device_type = "cpu"

    def jax_device(self):
        return jax.devices("cpu")[self.device_id % len(jax.devices("cpu"))]


class TPUPlace(Place):
    device_type = "tpu"


# CUDAPlace parity alias: on this framework "gpu" means the accelerator (TPU).
CUDAPlace = TPUPlace
XPUPlace = TPUPlace
CustomPlace = TPUPlace


@functools.lru_cache(None)
def _accelerator_available() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def is_tpu_backend() -> bool:
    """True when the default JAX backend is the TPU.  THE single predicate
    for fast-path dispatch (Pallas kernels compiled by Mosaic vs interpret
    mode, hardware RNG) — call it, don't spell the platform name again."""
    return jax.default_backend() == "tpu"


#: Parent of the package directory: the checkout in a dev tree.  A FIXED
#: path on purpose — the directory is part of the cache key's lookup, so a
#: tempdir/pid/time-derived path would never hit.
_DEFAULT_COMPILE_CACHE = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it
    itself, nothing is touched); otherwise ``<checkout>/.jax_cache``.
    Entry points call this (chip_smoke.py, bench.py, replica_main) — never
    at import, never from the test suite."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_COMPILE_CACHE)
    return _DEFAULT_COMPILE_CACHE


_current_place: Place | None = None


def set_device(device: str):
    """paddle.set_device parity: 'tpu', 'tpu:0', 'cpu', 'gpu' (alias of tpu)."""
    global _current_place
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    if name in ("tpu", "gpu", "xpu", "npu", "custom_device"):
        if not _accelerator_available():
            raise RuntimeError(
                f"set_device({device!r}): JAX sees no TPU "
                f"(devices: {jax.devices()})")
        _current_place = TPUPlace(idx)
    elif name == "cpu":
        _current_place = CPUPlace(idx)
    else:
        raise ValueError(f"unknown device {device!r}")
    return _current_place


def get_device() -> str:
    p = _get_place()
    return f"{p.device_type}:{p.device_id}"


def _get_place() -> Place:
    global _current_place
    if _current_place is None:
        # no explicit request: follow JAX's default backend
        _current_place = TPUPlace(0) if is_tpu_backend() else CPUPlace(0)
    return _current_place


def is_compiled_with_cuda() -> bool:  # parity shim
    return False


def is_compiled_with_tpu() -> bool:
    return _accelerator_available()


def default_jax_device():
    return _get_place().jax_device()
