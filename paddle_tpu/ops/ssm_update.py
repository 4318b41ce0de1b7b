"""Mamba-2 recurrent state: the single-token update and the chunked scan.

A Mamba-2 mixer keeps two pieces of state a sequence: the last K-1 inputs of
its causal depthwise convolution and, a head, the matrix S [head_dim, N] of

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

(A a negative scalar a head; B_t, C_t [N] shared by the heads of a group).

``ssm_update`` advances every serving slot by ONE token (the decode tick):
the convolution's shift, bias and SiLU, softplus(dt), the decay, the outer
product, the readout and the D skip.  The state pass — 2 MB a slot a layer at
the published sizes, read and written once — is one Pallas kernel
(``ssm_update``) that updates the state in place; the few [slots, channels]
vectors around it are plain XLA.  ``ssd_chunk_scan`` is the same recurrence
over a chunk of T tokens from an initial state, in the chunked
(state-space-dual) form: plain ``jnp`` in float32 (named scope
``ssd_chunk_scan``; not a kernel yet).

Both take ``n_valid``: the leading tokens of a row that are real.  A row
with ``n_valid = 0`` (an idle slot of the decode batch, a slot between two
prefill chunks) is not advanced at all, and a chunk's padded tail neither
decays nor shifts anything: dt = 0 there, and the convolution's state is
taken from the last real inputs.

Layouts (chosen so nothing is padded in HBM): the SSM state is float32
[slots, heads, head_dim * N] (one head's matrix flattened, N on the lanes),
the convolution's state [slots, (K-1) * channels] in the activations' dtype,
oldest tap first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._prng import interpret_default as _interpret_default

HI = jax.lax.Precision.HIGHEST
_SUB = 8  # heads a kernel step: one f32 vreg of sublanes


def _softplus(x):
    return jnp.where(x > 20.0, x, jnp.log1p(jnp.exp(jnp.minimum(x, 20.0))))


# ------------------------------------------------------------ convolution
def conv_step(conv_state, xbc, weight, bias, valid):
    """One token through the causal depthwise convolution.  conv_state
    [B, (K-1)*C] (oldest tap first), xbc [B, C], weight [K, C], bias [C],
    valid bool [B].  Returns (silu(conv) float32 [B, C], new state)."""
    K, C = weight.shape
    window = jnp.concatenate([conv_state, xbc.astype(conv_state.dtype)], axis=1)
    w = weight.astype(jnp.float32)
    acc = bias.astype(jnp.float32)[None, :]
    for k in range(K):
        acc = acc + window[:, k * C:(k + 1) * C].astype(jnp.float32) * w[k][None, :]
    new_state = jnp.where(valid[:, None], window[:, C:], conv_state)
    return jax.nn.silu(acc), new_state


def conv_chunk(conv_state, xbc, weight, bias, n_valid):
    """T tokens through the convolution from `conv_state`.  xbc [B, T, C],
    n_valid int32 [B].  The new state holds the K-1 inputs that precede
    position n_valid (so n_valid = 0 leaves it as it was)."""
    K, C = weight.shape
    B, T, _ = xbc.shape
    ext = jnp.concatenate(
        [conv_state.reshape(B, K - 1, C), xbc.astype(conv_state.dtype)], axis=1)
    w = weight.astype(jnp.float32)
    acc = jnp.broadcast_to(bias.astype(jnp.float32), (B, T, C))
    for k in range(K):
        acc = acc + ext[:, k:k + T].astype(jnp.float32) * w[k]
    tail = jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, K - 1, 0))(
        ext, n_valid.astype(jnp.int32))
    return jax.nn.silu(acc), tail.reshape(B, (K - 1) * C)


# ------------------------------------------------------- the state kernel
def _state_kernel(s_ref, xdt_ref, da_ref, b_ref, c_ref, o_ref, y_ref, *,
                  heads, head_dim, n_state):
    """One slot: every head's S <- dA S + (dt x) (x) B, y = S C.  Heads ride
    the sublanes eight at a time (one group's B and C serve all eight), a
    head's matrix is flat on the lanes, and position p of it is the lane
    block [p*N, (p+1)*N)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (_SUB, head_dim), 1)

    def group(g, carry):
        rows = pl.ds(pl.multiple_of(g * _SUB, _SUB), _SUB)
        xdt = xdt_ref[0, rows, :]           # [8, P]  dt * x
        da = da_ref[0, rows, :]             # [8, N]  exp(dt A), lane-replicated
        bg = b_ref[0, pl.ds(g, 1), :]       # [1, N]
        cg = c_ref[0, pl.ds(g, 1), :]
        y = jnp.zeros((_SUB, head_dim), jnp.float32)
        for p in range(head_dim):
            cols = slice(p * n_state, (p + 1) * n_state)
            s = s_ref[0, rows, cols] * da + xdt[:, p:p + 1] * bg
            o_ref[0, rows, cols] = s
            yc = jnp.sum(s * cg, axis=1, keepdims=True)  # [8, 1]
            y = jnp.where(lane == p, yc, y)
        y_ref[0, rows, :] = y
        return carry

    jax.lax.fori_loop(0, heads // _SUB, group, 0)


def _state_pallas(state, xdt, da, bm, cm, interpret):
    B, H, PN = state.shape
    P = xdt.shape[-1]
    N = PN // P
    G = bm.shape[1]
    kernel = functools.partial(_state_kernel, heads=H, head_dim=P, n_state=N)
    row = lambda *shape: pl.BlockSpec((1,) + shape, lambda b: (b, 0, 0))  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[row(H, PN), row(H, P), row(H, N), row(G, N), row(G, N)],
        out_specs=[row(H, PN), row(H, P)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, P), jnp.float32)],
        input_output_aliases={0: 0},  # the state is updated in place
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="ssm_update",
    )(state, xdt, da, bm, cm)


def _state_dense(state, xdt, da, bm, cm):
    B, H, PN = state.shape
    P = xdt.shape[-1]
    N = PN // P
    rep = H // bm.shape[1]
    s = state.reshape(B, H, P, N).astype(jnp.float32)
    bh = jnp.repeat(bm, rep, axis=1)  # [B, H, N]
    ch = jnp.repeat(cm, rep, axis=1)
    s = s * da[:, :, :1, None] + xdt[..., None] * bh[:, :, None, :]
    y = jnp.sum(s * ch[:, :, None, :], axis=-1)
    return s.reshape(B, H, PN).astype(state.dtype), y


def kernel_ok(state, head_dim, groups):
    """The kernel's tiling: eight heads of ONE group a step, N on the lanes."""
    _, H, PN = state.shape
    n_state = PN // head_dim
    return (state.dtype == jnp.float32 and H % _SUB == 0 and n_state % 128 == 0
            and H // groups == _SUB)


def ssm_update(ssm_state, conv_state, xbc, dt, *, conv_weight, conv_bias,
               a_log, dt_bias, d_skip, groups, n_state, valid,
               use_kernel=None, interpret=None):
    """Advance every slot one token.  ssm_state [B, H, P*N], conv_state
    [B, (K-1)*C], xbc [B, C] (C = H*P + 2*groups*N), dt [B, H] before the
    bias, valid bool [B].  Returns (y float32 [B, H*P] with the D skip,
    new ssm_state, new conv_state)."""
    B, H, PN = ssm_state.shape
    P = PN // n_state
    with jax.named_scope("ssm_update"):
        act, conv_new = conv_step(conv_state, xbc, conv_weight, conv_bias, valid)
        x = act[:, :H * P].reshape(B, H, P)
        bm = act[:, H * P:H * P + groups * n_state].reshape(B, groups, n_state)
        cm = act[:, H * P + groups * n_state:].reshape(B, groups, n_state)
        dtv = _softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        dtv = jnp.where(valid[:, None], dtv, 0.0)  # an idle row: S stays S
        a = -jnp.exp(a_log.astype(jnp.float32))
        da = jnp.broadcast_to(jnp.exp(dtv * a)[:, :, None], (B, H, n_state))
        xdt = x * dtv[:, :, None]
        if use_kernel is None:
            use_kernel = kernel_ok(ssm_state, P, groups)
        if use_kernel:
            if interpret is None:
                interpret = _interpret_default()
            s_new, y = _state_pallas(ssm_state, xdt, da, bm, cm, interpret)
        else:
            s_new, y = _state_dense(ssm_state, xdt, da, bm, cm)
        y = y + d_skip.astype(jnp.float32)[None, :, None] * x
    return y.reshape(B, H * P), s_new, conv_new


# ----------------------------------------------------------- chunked scan
def ssd_chunk_scan(x, dt, a, bm, cm, init_state, chunk_size=128):
    """The recurrence over T tokens in chunks (Mamba-2's SSD), float32.
    x [B, T, H, P], dt [B, T, H] (after softplus; 0 where a token is not
    real), a [H] (negative), bm, cm [B, T, G, N], init_state [B, H, P, N].
    Returns (y [B, T, H, P] without the D skip, final state)."""
    with jax.named_scope("ssd_chunk_scan"):
        B, T, H, P = x.shape
        G, N = bm.shape[2], bm.shape[3]
        Q = min(chunk_size, T)
        if T % Q:
            raise ValueError(f"{T} tokens are not whole chunks of {Q}")
        nc, rep = T // Q, H // G
        f32 = jnp.float32
        x, dt = x.astype(f32), dt.astype(f32)
        xc = (x * dt[..., None]).reshape(B, nc, Q, H, P)
        bc = jnp.repeat(bm.astype(f32), rep, axis=2).reshape(B, nc, Q, H, N)
        cc = jnp.repeat(cm.astype(f32), rep, axis=2).reshape(B, nc, Q, H, N)
        acum = jnp.cumsum((dt * a.astype(f32)).reshape(B, nc, Q, H), axis=2)
        # within a chunk: y_t += sum_{s<=t} (C_t.B_s) exp(A_t - A_s) dt_s x_s
        seg = acum[:, :, :, None, :] - acum[:, :, None, :, :]  # [B,nc,t,s,H]
        tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
        decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
        cb = jnp.einsum("bcthn,bcshn->bctsh", cc, bc, precision=HI)
        y = jnp.einsum("bctsh,bcshp->bcthp", cb * decay, xc, precision=HI)
        # each chunk's own contribution to the state at its end
        to_end = jnp.exp(acum[:, :, -1:, :] - acum)               # [B,nc,Q,H]
        own = jnp.einsum("bcsh,bcshp,bcshn->bchpn", to_end, xc, bc, precision=HI)
        chunk_decay = jnp.exp(acum[:, :, -1, :])                  # [B,nc,H]

        def carry(s, inp):
            dec, add = inp
            return s * dec[:, :, None, None] + add, s  # emits the state BEFORE

        final, before = jax.lax.scan(
            carry, init_state.astype(f32),
            (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(own, 1, 0)))
        before = jnp.moveaxis(before, 0, 1)                       # [B,nc,H,P,N]
        y = y + jnp.einsum("bcthn,bchpn->bcthp", cc * jnp.exp(acum)[..., None],
                           before, precision=HI)
        return y.reshape(B, T, H, P), final


def ssm_chunk(ssm_state, conv_state, xbc, dt, *, conv_weight, conv_bias, a_log,
              dt_bias, d_skip, groups, n_state, n_valid, chunk_size=128):
    """Advance rows by up to T tokens each (a prefill chunk).  ssm_state
    [B, H, P*N] and conv_state [B, (K-1)*C] are the rows' own; xbc
    [B, T, C], dt [B, T, H], n_valid int32 [B].  Returns (y float32
    [B, T, H*P] with the D skip, new ssm_state, new conv_state)."""
    B, H, PN = ssm_state.shape
    T = xbc.shape[1]
    act, conv_new = conv_chunk(conv_state, xbc, conv_weight, conv_bias, n_valid)
    P = PN // n_state
    x = act[..., :H * P].reshape(B, T, H, P)
    bm = act[..., H * P:H * P + groups * n_state].reshape(B, T, groups, n_state)
    cm = act[..., H * P + groups * n_state:].reshape(B, T, groups, n_state)
    dtv = _softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    real = jnp.arange(T)[None, :] < n_valid[:, None]
    dtv = jnp.where(real[..., None], dtv, 0.0)
    a = -jnp.exp(a_log.astype(jnp.float32))
    y, final = ssd_chunk_scan(x, dtv, a, bm, cm,
                              ssm_state.reshape(B, H, P, n_state), chunk_size)
    y = y + d_skip.astype(jnp.float32)[None, None, :, None] * x
    return (y.reshape(B, T, H * P), final.reshape(B, H, PN).astype(ssm_state.dtype),
            conv_new)
