"""Bring-up checks that need no chip (ISSUE 21).

Tier-1: chip_smoke.py refuses to run off the chip; Pallas kernels called
through `sharding_ctx.shard_kernel` on the 8-device CPU mesh equal the
unsharded call.  `slow`: every Pallas kernel AOT-compiles for a v5e 2x2
topology with Mosaic on (the only way short of a chip to see "Mosaic
kernels cannot be automatically partitioned"), and the smoke's own phase
functions run end to end at a tiny size.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.distributed import build_mesh
from paddle_tpu.distributed.sharding_ctx import (local_shape, mesh_scope,
                                                 shard_index, shard_kernel)
from paddle_tpu.ops import encoder_attention as enc
from paddle_tpu.ops import fused_ln
from paddle_tpu.ops.flash_attention import flash_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BSHD = "b-h-"


@pytest.fixture
def smoke(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    return chip_smoke


def test_smoke_refuses_off_chip(smoke, capsys):
    """In-process (the suite runs under JAX_PLATFORMS=cpu): main() must exit
    non-zero at the device gate, name the platform, and print no result."""
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code not in (0, None) and "'cpu'" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_fails_an_engine_that_never_ran_ahead(smoke):
    """serve_phase and state_phase report stats()["tick_pipeline"] through
    _ran_ahead, which fails the run where no decode result was read with the
    next program already dispatched (a pump that quietly drains every tick)."""
    class Engine:
        def __init__(self, overlapped):
            self.pl = {"overlapped": overlapped, "surplus_tokens": 0,
                       "drained": {"idle": 3}}

        def stats(self):
            return {"tick_pipeline": self.pl}

    assert smoke._ran_ahead(Engine(7))["overlapped"] == 7
    with pytest.raises(AssertionError, match="next program dispatched"):
        smoke._ran_ahead(Engine(0))


def _grads(fn, args, n_diff):
    def loss(*a):
        o = fn(*a)
        return jnp.sum(o * jnp.cos(o))

    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(n_diff))))(*args)


def _assert_same(got, want, tol):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=tol, atol=tol)


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(dp=2, sharding=2, mp=2)


def test_flash_through_mesh_helper(mesh):
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(4, 128, 4, 64).astype(np.float32))
               for _ in range(3))
    kern = lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa: E731
    want = _grads(kern, (q, k, v), 3)
    with mesh_scope(mesh):
        got = _grads(shard_kernel(kern, (BSHD,) * 3, BSHD), (q, k, v), 3)
    _assert_same(got, want, 2e-4)


def test_encoder_attention_through_mesh_helper(mesh):
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(4, 128, 2, 64).astype(np.float32))
               for _ in range(3))
    seed = jnp.zeros((2,), jnp.int32)
    kern = lambda q, k, v, s: enc.encoder_attention(q, k, v, seed=s)  # noqa: E731
    want = _grads(kern, (q, k, v, seed), 3)
    with mesh_scope(mesh):
        got = _grads(shard_kernel(kern, (BSHD,) * 3 + ("",), BSHD),
                     (q, k, v, seed), 3)
    _assert_same(got, want, 2e-4)


def test_fused_ln_through_mesh_helper(mesh):
    rng = np.random.RandomState(2)
    x, res = (jnp.asarray(rng.randn(8, 16, 128).astype(np.float32))
              for _ in range(2))
    g = jnp.asarray(rng.rand(128).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(128).astype(np.float32))
    seed = jnp.zeros((2,), jnp.int32)
    rows = "b--"

    def kern(x, res, g, b, seed, rate=0.0):
        return fused_ln.fused_dropout_add_layer_norm(
            x, res, g, b, seed + shard_index(), rate, 1e-5)

    want = _grads(kern, (x, res, g, b, seed), 4)
    with mesh_scope(mesh):
        wrap = lambda f: shard_kernel(f, (rows, rows, "", "", ""), rows)  # noqa: E731
        got = _grads(wrap(kern), (x, res, g, b, seed), 4)
        # identical rows on every data shard, dropout on: shard_index() must
        # give each shard its own mask (the block id restarts at 0 per shard)
        same = jnp.broadcast_to(x[:1], x.shape)
        out = jax.jit(wrap(lambda *a: kern(*a, rate=0.5)))(
            same, jnp.zeros_like(res), g, b, seed)
    _assert_same(got, want, 2e-4)
    out = np.asarray(out)
    assert not np.array_equal(out[:2], out[2:4])


def test_mesh_helper_rejects_non_dividing_shapes(mesh):
    q = jnp.zeros((4, 128, 3, 64), jnp.float32)  # 3 heads over mp=2
    kern = lambda q, k, v: flash_attention(q, k, v)  # noqa: E731
    assert shard_kernel(kern, (BSHD,) * 3, BSHD) is kern  # no mesh: identity
    assert local_shape(q.shape, BSHD) == q.shape
    with mesh_scope(mesh):
        assert local_shape((4, 128, 4, 64), BSHD) == (1, 128, 2, 64)
        with pytest.raises(ValueError, match=r"\(4, 128, 3, 64\).*heads"):
            shard_kernel(kern, (BSHD,) * 3, BSHD)(q, q, q)
        with pytest.raises(ValueError, match=r"\(6, 128, 4, 64\).*batch"):
            local_shape((6, 128, 4, 64), BSHD)


# --------------------------------------------------------------- slow: AOT
@pytest.fixture(scope="module")
def v5e_devices():
    """The four devices of a v5e 2x2 the installed libtpu can compile for
    with no chip attached."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"cannot create a v5e:2x2 topology here: {e!r}")
    return topo.devices


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Every dispatch site and kernel behaves as on the chip: Mosaic
    compilation (interpret=False), TPU-only kernels selected."""
    from paddle_tpu.core import device

    monkeypatch.setattr(device, "is_tpu_backend", lambda: True)


def _mosaic_calls(fn, sharding, *shapes_dtypes):
    """AOT-compile fn for the sharding's (chipless) TPU devices; return the
    op_name of each Mosaic custom call in the optimized HLO."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes_dtypes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return [re.search(r'op_name="([^"]*)"', ln).group(1)
            for ln in hlo.splitlines() if "tpu_custom_call" in ln]


def _one_device(devs):
    return NamedSharding(Mesh(np.array(devs[:1]), ("x",)), P())


def _names(calls, *want):
    return all(any(w in c for c in calls) for w in want)


@pytest.mark.slow
def test_aot_flash_and_helper_under_2x2_mesh(v5e_devices, as_on_tpu):
    bf = jnp.bfloat16
    qkv = [((4, 2048, 32, 128), bf)] * 3

    def loss(q, k, v, wrap=lambda f: f):
        o = wrap(lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))
    calls = _mosaic_calls(grad, _one_device(v5e_devices), *qkv)
    assert _names(calls, "flash_fwd", "flash_dq", "flash_dkv"), calls

    from paddle_tpu.distributed.topology import AXIS_ORDER

    mesh = Mesh(np.array(v5e_devices).reshape(1, 1, 2, 1, 2), AXIS_ORDER)
    sharded = NamedSharding(mesh, P("sharding", None, "mp", None))
    # plain GSPMD over a real mesh: the failure ShardedTrainStep used to hit
    with pytest.raises(NotImplementedError, match="Mosaic kernels cannot be "
                       "automatically partitioned"):
        _mosaic_calls(grad, sharded, *qkv)

    def grad_through_helper(q, k, v):
        with mesh_scope(mesh):
            return jax.grad(lambda *a: loss(*a, wrap=lambda f: shard_kernel(
                f, (BSHD,) * 3, BSHD)), argnums=(0, 1, 2))(q, k, v)

    calls = _mosaic_calls(grad_through_helper, sharded, *qkv)
    assert _names(calls, "flash_fwd", "flash_dq", "flash_dkv"), calls


@pytest.mark.slow
def test_aot_encoder_kernels(v5e_devices, as_on_tpu):
    one = _one_device(v5e_devices)
    bf, i32 = jnp.bfloat16, jnp.int32
    for causal, rate in ((False, 0.1), (True, 0.0)):
        def attn(q, k, v, seed):
            o = enc.encoder_attention(q, k, v, seed=seed, dropout_rate=rate,
                                      causal=causal)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        calls = _mosaic_calls(jax.grad(attn, argnums=(0, 1, 2)), one,
                              *[((32, 128, 12, 64), bf)] * 3, ((2,), i32))
        assert _names(calls, "encoder_attention_fwd",
                      "encoder_attention_bwd"), calls
    for rate in (0.0, 0.1):
        def ln(x, res, g, b, seed):
            o = fused_ln.fused_dropout_add_layer_norm(x, res, g, b, seed,
                                                      rate, 1e-5)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        calls = _mosaic_calls(
            jax.grad(ln, argnums=(0, 1, 2, 3)), one,
            ((4096, 768), bf), ((4096, 768), bf), ((768,), bf), ((768,), bf),
            ((2,), i32))
        assert _names(calls, "fused_ln_fwd", "fused_ln_bwd"), calls


@pytest.mark.slow
def test_aot_decode_kernels(v5e_devices, as_on_tpu):
    from paddle_tpu.ops import decode_attention as da

    one = _one_device(v5e_devices)
    bf, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32
    H, D, page, pages, M, B = 32, 128, 128, 129, 16, 8
    for S, b in ((1, B), (5, B), (256, 1)):
        for kv, scales in ((bf, ()), (i8, (((pages, H, page), f32),) * 2)):
            calls = _mosaic_calls(
                lambda q, k, v, off, tbl, *sc: da.paged_decode_attention(
                    q, k, v, off, tbl, *sc), one,
                ((b, S, H, D), bf), ((pages, H, page, D), kv),
                ((pages, H, page, D), kv), ((b,), i32), ((b, M), i32), *scales)
            assert _names(calls, "paged_attention"), (S, kv, calls)
    L = 1024
    for kv, scales in ((bf, ()), (i8, (((B, 16, L), f32),) * 2)):
        calls = _mosaic_calls(
            lambda q, k, v, off, *sc: da.decode_attention(q, k, v, off, *sc),
            one, ((B, 1, 16, D), bf), ((B, 16, L, D), kv), ((B, 16, L, D), kv),
            ((B,), i32), *scales)
        assert _names(calls, "decode_attention"), (kv, calls)


@pytest.mark.slow
def test_aot_hybrid_kernels_at_published_widths(v5e_devices, as_on_tpu):
    """Nemotron-3-Nano's widths: the grouped expert matmul at a decode tick's
    and a prefill chunk's rows, the Mamba-2 state update over 64 slots, and
    the paged kernel at 32 query heads on 2 K/V heads (a chunk of 256 rides
    it as two sub-blocks)."""
    from paddle_tpu.ops import decode_attention as da
    from paddle_tpu.ops import moe_experts as moe
    from paddle_tpu.ops import ssm_update as ssm

    one = _one_device(v5e_devices)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    h, F, held = 2688, 1856, 64
    for T in (64, 256):
        calls = _mosaic_calls(
            lambda x, w1, w2, ex, wt: moe.moe_experts(x, w1, w2, ex, wt, 0)[0],
            one, ((T, h), bf), ((held, F, h), bf), ((held, F, h), bf),
            ((T, 6), i32), ((T, 6), f32))
        assert _names(calls, "moe_experts"), (T, calls)
    B, H, P, N, G = 64, 64, 64, 128, 8
    R, _, L = ssm.state_shape(H, P, N, G)
    assert ssm.kernel_ok(jax.ShapeDtypeStruct((B, R, N, L), f32), G)
    calls = _mosaic_calls(
        lambda s, xdt, dA, bm, cm: ssm._state_pallas(s, xdt, dA, bm, cm, False),
        one, ((B, R, N, L), f32), ((B, R, L), f32), ((B, R, L), f32),
        ((B, G, N), f32), ((B, G, N), f32))
    assert sum("ssm_update" in c for c in calls) == 1, calls
    for S, b, n in ((1, 64, 1), (256, 1, 2)):
        calls = _mosaic_calls(
            lambda q, k, v, off, tbl: da.paged_decode_attention(q, k, v, off, tbl),
            one, ((b, S, 32, 128), bf), ((513, 2, 128, 128), bf),
            ((513, 2, 128, 128), bf), ((b,), i32), ((b, 8), i32))
        assert sum("paged_attention" in c for c in calls) == n, (S, calls)


@pytest.mark.slow
def test_aot_sparse_and_lightning_kernels_at_published_widths(v5e_devices, as_on_tpu):
    """MiniCPM-SALA's widths at the cell's sizes: the block-sparse decode pass
    (64 slots, 2 K/V heads of 16 query heads, lists of 128 blocks, pages of
    two blocks) and the lightning state pass (32 heads of 128 x 128 float32:
    PR 30's kernel with one "group" a head, under this layer's name)."""
    from paddle_tpu.ops import lightning_attention as la
    from paddle_tpu.ops import sparse_attention as sa

    one = _one_device(v5e_devices)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    B, Hq, H, D, P, M = 64, 32, 2, 128, 1681, 262
    spec = sa.SparseSpec()
    calls = _mosaic_calls(
        lambda q, k, v, tbl, idx, cnt, n: sa.sparse_paged_attention(
            q, k, v, tbl, idx, cnt, n, spec),
        one, ((B, Hq, D), bf), ((P, H, 128, D), bf), ((P, H, 128, D), bf),
        ((B, M), i32), ((B, H, spec.list_len), i32), ((B,), i32), ((B,), i32))
    assert sum("sparse_paged_attention" in c for c in calls) == 1, calls
    calls = _mosaic_calls(
        lambda s, q, k, v, ok: la.lightning_update(s, q, k, v, la.log_decay(32), ok),
        one, ((B, 32, D, D), f32), ((B, 32, D), bf), ((B, 32, D), bf),
        ((B, 32, D), bf), ((B,), jnp.bool_))
    assert sum("lightning_update" in c for c in calls) == 1, calls


@pytest.mark.slow
def test_aot_latent_and_gated_expert_kernels_at_published_widths(v5e_devices, as_on_tpu):
    """Kanana-2's widths at the cell's sizes: the latent decode pass (64
    slots, 32 heads on rows of 512 + 64 values in 640 lanes, tables of 134
    pages) and the gated experts (128 held, three 768 x 2048 matrices each) at
    a decode tick's rows and at a chunk's."""
    from paddle_tpu.ops import latent_attention as la
    from paddle_tpu.ops import moe_experts as moe

    one = _one_device(v5e_devices)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    B, H, P, M = 64, 32, 1793, 134
    calls = _mosaic_calls(
        lambda qa, qr, pool, tbl, n: la.latent_decode_attention(
            qa, qr, pool, tbl, n, 192 ** -0.5),
        one, ((B, H, 512), bf), ((B, H, 64), bf), ((P, 128, 640), bf),
        ((B, M), i32), ((B,), i32))
    assert sum("latent_attention" in c for c in calls) == 1, calls
    for T in (64, 256):
        calls = _mosaic_calls(
            lambda x, wg, w1, w2, idx, w: moe.moe_experts(x, w1, w2, idx, w, 0,
                                                          w_gate=wg)[0],
            one, ((T, 2048), bf), *[((128, 768, 2048), bf)] * 3,
            ((T, 6), i32), ((T, 6), f32))
        assert sum("moe_glu_experts" in c for c in calls) == 1, (T, calls)


@pytest.mark.slow
def test_aot_sampler_keeps_its_conditionals_at_a_64k_vocabulary(v5e_devices):
    """The v5e's compiler leaves the fused sampler's two conditionals in the
    decode scan, with the ONE sort of `[64, 65536]` inside the inner branch:
    a greedy tick can skip it (the chip run that showed it does: PERF.md §6,
    PR 28)."""
    from paddle_tpu.inference.llm_server import _select_rows

    B, V = 64, 65536
    one = _one_device(v5e_devices)

    def decode(logits, key, do_s, temp, k, p, mask):
        def tick(c, key):
            nxt = _select_rows(logits * c.astype(jnp.bfloat16), key, do_s,
                               temp, k, p, token_mask=mask)
            return c + nxt.sum(), nxt
        return jax.lax.scan(tick, jnp.int32(1), jax.random.split(key, 2))

    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((B, V), jnp.bfloat16), ((2,), jnp.uint32), ((B,), jnp.bool_),
        ((B,), jnp.float32), ((B,), jnp.int32), ((B,), jnp.float32),
        ((B, V), jnp.bool_))]
    hlo = jax.jit(decode).lower(*args).compile().as_text()
    assert len(re.findall(r" conditional\(", hlo)) == 2
    sorts = [ln for ln in hlo.splitlines()
             if re.search(r" sort\(", ln) and f"[{B},{V}]" in ln]
    assert len(sorts) == 1
    assert "sampler/cond/branch_1_fun/cond/branch_1_fun" in sorts[0]


# --------------------------------------------- slow: the smoke's control flow
@pytest.mark.slow
def test_smoke_phases_at_tiny_size_on_cpu(smoke, monkeypatch):
    """chip_smoke.py's own phase functions, head_dim and page size kept at
    128 so the ragged paged kernel (interpret mode) is the path taken.  The
    two checks only a chip can pass are stubbed."""
    monkeypatch.setattr(smoke, "_check_flash_in_hlo", lambda *a: None)
    monkeypatch.setattr(smoke, "_check_memory", lambda *a: None)
    tiny = dict(hidden_size=256, intermediate_size=512, vocab_size=512,
                num_attention_heads=2, num_key_value_heads=2)
    geom = smoke.serve_phase(n_layers=2, max_seq_len=512,
                             prompt_lens=(40, 440), new_tokens=8,
                             max_position_embeddings=512, **tiny)
    assert set(smoke.kernel_phase(**geom)) == {
        "S1_bf16", "S1_int8", "S256_bf16", "S256_int8", "S5_bf16", "S5_int8",
        "chat_bf16"}
    assert smoke.state_phase(hidden=128) < 0.05
    assert smoke.latent_phase(doc_tokens=150) < 0.05
    losses = smoke.train_phase(n_layers=2, batch=4, seq=128, **tiny)
    assert smoke.mesh_phase(losses[0], n_layers=2, batch=4, seq=128,
                            **tiny) is not None
