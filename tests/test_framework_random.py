"""framework/random.Generator: the stateful key and its host-side offset.

`split()` / `get_rng_key()` are the eager stream initialisers, dropout and
the train step draw from; `fork()` hands `(key, offset)` to compiled
programs that derive their own keys and must not move that stream."""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.framework import random as fr

pytestmark = pytest.mark.quick


def _data(key):
    return np.asarray(jax.random.key_data(key))


def test_fork_advances_only_the_offset(monkeypatch):
    gen = fr.Generator(11)
    before = _data(gen.key)

    def boom(*a, **k):
        raise AssertionError("fork() must not touch the device")

    with monkeypatch.context() as m:
        for name in ("split", "fold_in", "key", "key_data"):
            m.setattr(jax.random, name, boom)
        pairs = [gen.fork() for _ in range(3)]
    assert [int(o) for _, o in pairs] == [0, 1, 2]
    assert all(isinstance(o, np.uint32) for _, o in pairs)
    assert all(k is gen.key for k, _ in pairs)  # the resident array itself
    assert (_data(gen.key) == before).all()
    assert gen.initial_seed() == 11


@pytest.mark.parametrize("reset", ["manual_seed", "set_key", "paddle.seed"])
def test_reseeding_resets_the_offset(reset):
    gen = fr.default_generator() if reset == "paddle.seed" else fr.Generator(3)
    gen.fork(), gen.fork()
    if reset == "manual_seed":
        gen.manual_seed(4)
    elif reset == "set_key":
        gen.set_key(fr.make_key(4))
    else:
        paddle.seed(4)
    key, offset = gen.fork()
    assert int(offset) == 0
    assert (_data(key) == _data(fr.make_key(4))).all()


@pytest.mark.parametrize("forks", [0, 1, 5])
def test_split_stream_ignores_interleaved_forks(forks):
    plain = fr.Generator(21)
    want = [_data(plain.split()) for _ in range(4)]
    gen = fr.Generator(21)
    got = []
    for _ in range(4):
        for _ in range(forks):
            gen.fork()
        got.append(_data(gen.split()))
    assert all((a == b).all() for a, b in zip(want, got))
    assert gen._offset == 4 * forks  # split() does not reset it either


def test_offset_wraps_at_the_width_fold_in_takes():
    gen = fr.Generator(1)
    gen._offset = 2**32 - 1
    assert int(gen.fork()[1]) == 2**32 - 1
    assert int(gen.fork()[1]) == 0


def test_scoped_generator_starts_at_offset_zero():
    fr.default_generator().fork()
    with fr.rng_key_scope(fr.make_key(9)) as gen:
        assert fr.default_generator() is gen
        assert int(gen.fork()[1]) == 0


def test_forked_pairs_give_distinct_reproducible_keys_in_a_program():
    """What the serving programs do with the pair: fold the offset into the
    key inside jit.  Same (seed, offset) -> same keys, next offset -> other
    keys, and the eager stream is where it would have been."""
    derive = jax.jit(lambda k, o: jax.random.key_data(
        jax.random.split(jax.random.fold_in(k, o), 2)))
    paddle.seed(77)
    a0, a1 = (np.asarray(derive(*fr.default_generator().fork()))
              for _ in range(2))
    after = _data(fr.get_rng_key())
    paddle.seed(77)
    b0 = np.asarray(derive(*fr.default_generator().fork()))
    assert (a0 == b0).all() and not (a0 == a1).all()
    paddle.seed(77)
    assert (_data(fr.get_rng_key()) == after).all()
