"""paddle.utils.dlpack — zero-copy tensor exchange via the DLPack protocol.

Ref: python/paddle/utils/dlpack.py (to_dlpack/from_dlpack over pybind
capsules); here the capsule comes from the jax.Array __dlpack__ protocol.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..tensor.tensor import Tensor

__all__ = ["to_dlpack", "from_dlpack"]


def to_dlpack(x):
    """Export a Tensor (or jax.Array) as a DLPack capsule.  Devices whose PJRT
    plugin cannot hand out external buffer references
    fall back to a host copy — correct, just not zero-copy."""
    import numpy as np

    arr = x._value if isinstance(x, Tensor) else x
    try:
        return arr.__dlpack__()
    except Exception:
        return np.asarray(jax.device_get(arr)).__dlpack__()


def from_dlpack(dlpack):
    """Import a DLPack capsule (or any object exposing __dlpack__) as a Tensor."""
    if hasattr(dlpack, "__dlpack__"):
        arr = jnp.from_dlpack(dlpack)
    else:
        # a raw PyCapsule, e.g. produced by another framework's to_dlpack —
        # modern jax only takes protocol objects, so consume the capsule via
        # torch (which still accepts legacy capsules) and re-export
        import torch.utils.dlpack as _tdl

        arr = jnp.asarray(_tdl.from_dlpack(dlpack).numpy())
    return Tensor(arr)
