"""Mamba-2 recurrent state: the single-token update and the chunked scan.

A Mamba-2 mixer keeps two pieces of state a sequence: the last K-1 inputs of
its causal depthwise convolution and, a head, the matrix S [head_dim, N] of

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

(A a negative scalar a head; B_t, C_t [N] shared by the heads of a group).

``ssm_update`` advances every serving slot by ONE token (the decode tick):
the convolution's shift, bias and SiLU, softplus(dt), the decay, the outer
product, the readout and the D skip.  The state pass — 2 MB a slot a layer at
the published sizes, read and written once — is one Pallas kernel
(``ssm_update``) that updates the state in place at the rate the HBM
streams; the few [slots, channels] vectors around it are plain XLA.
``ssd_chunk_scan`` is the same recurrence over a chunk of T tokens from an
initial state, in the chunked (state-space-dual) form: plain ``jnp`` in
float32 (named scope ``ssd_chunk_scan``; not a kernel yet).

Both take ``n_valid``: the leading tokens of a row that are real.  A row
with ``n_valid = 0`` (an idle slot of the decode batch, a slot between two
prefill chunks) is not advanced at all, and a chunk's padded tail neither
decays nor shifts anything: dt = 0 there, and the convolution's state is
taken from the last real inputs.

Layouts (chosen so nothing is padded in HBM and the kernel's inner loop is
VALU work on whole vregs): the SSM state is float32 [slots, heads / k, N,
k * head_dim] — N on the SUBLANES, and on the lanes the k heads of one
group that fill 128 of them side by side (``state_shape``: k = 2 at the
published head_dim 64, [32, 128, 128] a slot).  S[h, p, n] is
state[h // k, n, (h % k) * head_dim + p].  So B and C are columns
broadcast along the lanes once a group, dt x and exp(dt A) are lane vectors
a row, and the readout y[h, p] = sum_n S[h, p, n] C[n] is a running
product-sum over a row's N / 8 vregs closed by ONE sublane reduction, which
leaves y in 128-lane rows.  (With N on the lanes every state vreg pays a
lane broadcast, a full lane reduction and a masked merge: the cross-lane
unit, not the HBM, then sets the pace.)  ``ssm_chunk`` transposes a row's
state to a matrix a head for the scan and back.  The convolution's state is
[slots, (K-1) * channels] in the activations' dtype, oldest tap first.
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
def state_shape(heads, head_dim, n_state, groups):
    """A slot's SSM state: [heads / k, N, k * head_dim], where k heads of
    ONE group lie side by side on a row's lanes: as many as fit 128 lanes
    and divide the group (2 at the published head_dim 64)."""
    per_group = heads // groups
    k = max(1, min(128 // head_dim, per_group))
    while per_group % k:
        k -= 1
    return (heads // k, n_state, k * head_dim)


def rows_to_heads(state, heads):
    """[B, H/k, N, k*P] (as kept) -> [B, H, P, N] (a matrix a head)."""
    B, R, N, L = state.shape
    k = heads // R
    return state.reshape(B, R, N, k, L // k).transpose(0, 1, 3, 4, 2).reshape(
        B, heads, L // k, N)


def heads_to_rows(s, rows):
    """[B, H, P, N] -> [B, H/k, N, k*P]."""
    B, H, P, N = s.shape
    k = H // rows
    return s.reshape(B, rows, k, P, N).transpose(0, 1, 4, 2, 3).reshape(
        B, rows, N, k * P)


def _state_kernel(s_ref, xdt_ref, da_ref, b_ref, c_ref, o_ref, y_ref, *, groups):
    """One slot: every head's S <- dA S + (dt x) (x) B, y = S C.  A vreg holds
    eight values of n on the sublanes and one row's (head, p) on the lanes:
    B and C are columns broadcast along the lanes once a group, dt x and dA
    lane vectors a row, and the readout accumulates S * C over the row's N / 8
    vregs with ONE sublane reduction at the row's end."""
    _, R, N, L = s_ref.shape
    per = R // groups
    for g in range(groups):
        bb = jnp.broadcast_to(b_ref[0, :, g:g + 1], (N, L))
        cb = jnp.broadcast_to(c_ref[0, :, g:g + 1], (N, L))

        def row(i, carry):
            r = g * per + i
            xdt = xdt_ref[0, pl.ds(r, 1), :]    # [1, L]  dt * x
            da = da_ref[0, pl.ds(r, 1), :]      # [1, L]  exp(dt A) of the lane's head
            acc = jnp.zeros((_SUB, L), jnp.float32)
            for n in range(0, N, _SUB):
                s = s_ref[0, r, n:n + _SUB, :] * da + xdt * bb[n:n + _SUB]
                o_ref[0, r, n:n + _SUB, :] = s
                acc = acc + s * cb[n:n + _SUB]
            y_ref[0, pl.ds(r, 1), :] = jnp.sum(acc, axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, per, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _state_pallas(state, xdt, da, bm, cm, interpret, name="ssm_update"):
    """state [B, R, N, L]; xdt, da [B, R, L]; bm, cm [B, G, N].  A jit of
    its own, so a program that calls it once a layer traces and lowers the
    kernel once, not once a layer (every process pays that in `warmup()`,
    compile cache or not).  `name` is the kernel's, hence its device
    events': another recurrence of this form passes its own."""
    B, R, N, L = state.shape
    G = bm.shape[1]
    bm, cm = jnp.swapaxes(bm, 1, 2), jnp.swapaxes(cm, 1, 2)  # n on the sublanes
    kernel = functools.partial(_state_kernel, groups=G)
    row = lambda *shape: pl.BlockSpec(  # noqa: E731
        (1,) + shape, lambda b: (b,) + (0,) * len(shape))
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[row(R, N, L), row(R, L), row(R, L), row(N, G), row(N, G)],
        out_specs=[row(R, N, L), row(R, L)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, R, L), jnp.float32)],
        input_output_aliases={0: 0},  # the state is updated in place
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name=name,
    )(state, xdt, da, bm, cm)


def _state_dense(state, xdt, da, bm, cm):
    """The same pass in plain jnp, same arguments."""
    rep = state.shape[1] // bm.shape[1]  # rows a group
    s = state.astype(jnp.float32) * da[:, :, None, :] \
        + xdt[:, :, None, :] * jnp.repeat(bm, rep, axis=1)[..., None]
    y = jnp.sum(s * jnp.repeat(cm, rep, axis=1)[..., None], axis=2)
    return s.astype(state.dtype), y


def kernel_ok(state, groups):
    """The kernel's tiling: whole vregs (eight n by 128 lanes of one group's
    heads), float32."""
    _, R, N, L = state.shape
    return (state.dtype == jnp.float32 and L % 128 == 0 and N % _SUB == 0
            and R % groups == 0)


def state_pass(state, xdt, da, bm, cm, name="ssm_update", use_kernel=None,
               interpret=None):
    """S <- da S + xdt (x) B, y = S C over every slot, by the kernel where
    the shape is its tiling's and in plain jnp elsewhere.  Arguments as
    `_state_pallas`; returns (new state, y float32 [B, R, L])."""
    if use_kernel is None:
        use_kernel = kernel_ok(state, bm.shape[1])
    if not use_kernel:
        return _state_dense(state, xdt, da, bm, cm)
    if interpret is None:
        interpret = _interpret_default()
    return _state_pallas(state, xdt, da, bm, cm, interpret, name)


def ssm_update(ssm_state, conv_state, xbc, dt, *, conv_weight, conv_bias,
               a_log, dt_bias, d_skip, groups, n_state, valid,
               use_kernel=None, interpret=None):
    """Advance every slot one token.  ssm_state [B, H/k, N, k*P] (see
    `state_shape`), conv_state [B, (K-1)*C], xbc [B, C] (C = H*P +
    2*groups*N), dt [B, H] before the bias, valid bool [B].  Returns (y
    float32 [B, H*P] with the D skip, new ssm_state, new conv_state)."""
    B, R, _, L = ssm_state.shape
    H = dt.shape[-1]
    P = L * R // H
    with jax.named_scope("ssm_update"):
        act, conv_new = conv_step(conv_state, xbc, conv_weight, conv_bias, valid)
        x = act[:, :H * P].reshape(B, H, P)
        bm = act[:, H * P:H * P + groups * n_state].reshape(B, groups, n_state)
        cm = act[:, H * P + groups * n_state:].reshape(B, groups, n_state)
        dtv = _softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        dtv = jnp.where(valid[:, None], dtv, 0.0)  # an idle row: S stays S
        a = -jnp.exp(a_log.astype(jnp.float32))
        # heads side by side on a row's lanes, as the state keeps them
        da = jnp.broadcast_to(jnp.exp(dtv * a)[:, :, None], (B, H, P)).reshape(B, R, L)
        xdt = (x * dtv[:, :, None]).reshape(B, R, L)
        s_new, y = state_pass(ssm_state, xdt, da, bm, cm,
                              use_kernel=use_kernel, interpret=interpret)
        y = y.reshape(B, H, P) + d_skip.astype(jnp.float32)[None, :, None] * x
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
    [B, H/k, N, k*P] and conv_state [B, (K-1)*C] are the rows' own; xbc
    [B, T, C], dt [B, T, H], n_valid int32 [B].  Returns (y float32
    [B, T, H*P] with the D skip, new ssm_state, new conv_state).  The scan
    works a matrix a head: the state is transposed in and out."""
    B, R, _, L = ssm_state.shape
    T, H = xbc.shape[1], dt.shape[-1]
    P = L * R // H
    act, conv_new = conv_chunk(conv_state, xbc, conv_weight, conv_bias, n_valid)
    x = act[..., :H * P].reshape(B, T, H, P)
    bm = act[..., H * P:H * P + groups * n_state].reshape(B, T, groups, n_state)
    cm = act[..., H * P + groups * n_state:].reshape(B, T, groups, n_state)
    dtv = _softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    real = jnp.arange(T)[None, :] < n_valid[:, None]
    dtv = jnp.where(real[..., None], dtv, 0.0)
    a = -jnp.exp(a_log.astype(jnp.float32))
    y, final = ssd_chunk_scan(x, dtv, a, bm, cm, rows_to_heads(ssm_state, H),
                              chunk_size)
    y = y + d_skip.astype(jnp.float32)[None, None, :, None] * x
    return (y.reshape(B, T, H * P),
            heads_to_rows(final, R).astype(ssm_state.dtype), conv_new)
