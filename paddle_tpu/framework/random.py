"""Stateful RNG facade over JAX threefry keys.

Reference: `phi/core/generator.h:23` (stateful per-device Generator) and
`paddle.seed` (`python/paddle/framework/random.py`).  JAX RNG is functional; we keep a
stateful key that is split on every draw.  Under `to_static`/jit tracing, the traced
program receives a fresh key argument each call via `push_key` so dropout masks are not
baked in as constants.

The generator also keeps a host-side offset beside its key, as the reference's generator
keeps a (seed, offset) pair.  `Generator.fork()` hands out `(key, offset)` and advances
only the offset, a Python integer: no device work.  The serving programs
(`inference/llm_server.py`) take that pair as arguments and derive their keys INSIDE the
compiled program (`split(fold_in(key, offset), n)`), so a decode tick dispatches no eager
device program for its randomness.  `split()` / `get_rng_key()` are eager device calls:
they belong to initialisers, dropout and the train step, never between a tick's compiled
calls.
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np


_RNG_IMPL = None  # resolved lazily: "rbg" on TPU, jax default elsewhere


def _rng_impl():
    """TPU uses the hardware RBG bit generator: dropout-mask generation for
    one ERNIE b512xs128 step measured 48.3 ms (threefry) vs 13.4 ms (rbg) on
    v5e — threefry burns VPU cycles hashing counters while rbg reads the
    on-chip RNG.  CPU/GPU keep the jax default (threefry) so host-side tests
    and golden sequences are unchanged.  Override with set_rng_impl()."""
    global _RNG_IMPL
    if _RNG_IMPL is None:
        from ..core.device import is_tpu_backend

        _RNG_IMPL = "rbg" if is_tpu_backend() else "threefry2x32"
    return _RNG_IMPL


def set_rng_impl(impl: str):
    """Force the PRNG implementation ('threefry2x32' | 'rbg'); takes effect at
    the next paddle.seed()/key creation."""
    global _RNG_IMPL
    _RNG_IMPL = impl


def make_key(seed: int):
    """Create a PRNG key with the framework-selected implementation.  EVERY
    key-creation site must use this (not bare jax.random.key/PRNGKey) or the
    TPU rbg fast path silently reverts to threefry for that stream."""
    return jax.random.key(int(seed), impl=_rng_impl())


class Generator:
    """Stateful key-splitting generator (ref phi/core/generator.h:23)."""

    def __init__(self, seed: int = 0):
        self._seed = int(seed)
        self._key = None  # lazy: don't touch the backend at import time
        self._offset = 0  # host-side draw counter under the current key

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.key(self._seed, impl=_rng_impl())
        return self._key

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        self._key = jax.random.key(self._seed, impl=_rng_impl())
        self._offset = 0
        return self

    def initial_seed(self) -> int:
        return self._seed

    def set_key(self, key):
        self._key = key
        self._offset = 0

    def split(self):
        self._key, sub = jax.random.split(self.key)
        return sub

    def fork(self):
        """`(key, offset)` for a compiled program that derives its own keys
        (`jax.random.fold_in(key, offset)` inside the program), then advance
        the offset.  Host-only: the resident key is handed out as it is and
        `split()`'s stream does not move.  The offset wraps at 2**32, the
        width `fold_in` takes."""
        offset = self._offset
        self._offset = (offset + 1) & 0xFFFFFFFF
        return self.key, np.uint32(offset)


_default_generator = Generator(np.random.randint(0, 2**31 - 1))
_key_stack: list[Generator] = []


def default_generator() -> Generator:
    return _key_stack[-1] if _key_stack else _default_generator


def seed(s: int):
    """paddle.seed parity."""
    _default_generator.manual_seed(s)
    return _default_generator


def get_rng_key():
    """Split the current generator and return a fresh subkey."""
    return default_generator().split()


@contextlib.contextmanager
def rng_key_scope(key):
    """Run a region drawing randomness from `key` (used by to_static tracing)."""
    gen = Generator(0)
    gen.set_key(key)
    _key_stack.append(gen)
    try:
        yield gen
    finally:
        _key_stack.pop()


def get_cuda_rng_state():  # parity shims
    return default_generator().key


def set_cuda_rng_state(state):
    default_generator().set_key(state)
