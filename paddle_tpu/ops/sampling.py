"""Fused on-device token sampling + speculative-decoding acceptance.

One sampling implementation for every decode surface: the solo compiled
``generate`` loop (``models/generation._select``), the serving engine's
per-slot ``_select_rows``, and the speculative verify programs.  Everything
here runs INSIDE the compiled decode/verify step — temperature, top-k and
top-p masking, the categorical draw, and the spec-decode accept/residual
sampling all stay on device, so the only thing copied to the host per
step is the token ids.

Per-row knobs ride as device ARRAYS (one entry per batch slot), so slots
with different sampling settings share one compiled program.  ``top_k`` is
per-row too: the k-th largest value is read out of the descending sort the
top-p mask needs anyway (``take_along_axis`` at index ``k-1``), so a
per-slot k never changes the program shape.

The cost follows the knobs.  The same arrays decide, ON DEVICE and inside
the one program (``lax.cond``; no second program, no flag), how much of the
sampler a step runs:

- no row with ``do_sample``: the ``argmax`` alone — no division by the
  temperature, no sort, no soft-max, no noise over ``[B, V]``;
- a row samples but no SAMPLING row has a live threshold (``top_k`` in
  ``[1, V)`` or ``top_p < 1``; a greedy row may carry a stale ``top_p``):
  scale and draw, no sort;
- else one sort of the vocabulary serves both thresholds.  The entries
  below the k-th value are a suffix of the descending sort, so masking that
  suffix in the sorted row IS the sort of the top-k survivors the top-p
  mass is summed over: the masked logits are bitwise those of sorting
  twice, and with the same key every draw is the same token.

Contracts the repo's parity tests pin down:

- greedy rows are a bare ``argmax`` — bitwise identical to
  ``generation._select`` and to the pre-fusion ``_select_rows``;
- the masking order is explicit token-mask -> temperature -> top-k ->
  top-p (top-p renormalizes over the top-k survivors), matching
  ``generation._select``; masks apply only where enabled (k in [1, V),
  p < 1, token mask all-True rows untouched), so disabled knobs are
  exact no-ops;
- ``spec_accept``'s greedy path accepts the longest draft prefix that
  matches the verifier's argmax ladder — by construction the emitted
  tokens are the verifier's own argmaxes, which is what makes speculative
  greedy decoding bitwise identical to non-speculative greedy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["has_threshold", "mask_logits", "sample_rows", "spec_accept"]


def has_threshold(top_k, top_p, vocab):
    """Per row: does a knob cut the candidate set?  ``top_k`` in
    ``[1, vocab)`` or ``top_p < 1``.  Plain comparisons, so the serving
    engine's host count (numpy knobs) and the compiled sampler (traced
    arrays) decide by the same line."""
    return ((top_k > 0) & (top_k < vocab)) | (top_p < 1.0)


def _cut(lt, k, top_p):
    """top-k by VALUE, then top-p over the survivors, from ONE sort."""
    V = lt.shape[-1]
    use_k, use_p = (k > 0) & (k < V), top_p < 1.0
    sorted_lt = jnp.sort(lt, axis=-1)[..., ::-1]
    # k-th largest value per row; masking by VALUE (< kth) keeps ties at
    # the threshold, exactly like generation._select's lax.top_k variant
    kth = jnp.take_along_axis(
        sorted_lt, jnp.clip(k - 1, 0, V - 1)[:, None], axis=-1)
    lt = jnp.where(use_k[:, None] & (lt < kth), -jnp.inf, lt)
    # top-p over the top-k SURVIVORS.  The entries below the k-th value are
    # a suffix of the descending sort, so masking that suffix IS the sort
    # of the masked row: no second sort
    sorted_lt = jnp.where(
        use_k[:, None] & (sorted_lt < kth), -jnp.inf, sorted_lt)
    probs = jax.nn.softmax(sorted_lt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep the smallest set with cumulative prob >= top_p (always >= 1 tok)
    cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(sorted_lt, cutoff_idx, axis=-1)
    return jnp.where(use_p[:, None] & (lt < cutoff), -jnp.inf, lt)


def mask_logits(logits, temperature, top_k, top_p, token_mask=None,
                rows=None):
    """Temperature/top-k/top-p masking, vectorized per row.

    logits ``[B, V]``; ``temperature``/``top_p`` f32 ``[B]``; ``top_k``
    int32 ``[B]`` (0, or >= V, disables).  Returns f32 logits with
    masked-out entries at ``-inf`` — feed to ``jax.random.categorical``
    (which normalizes) or ``softmax``.

    ``token_mask`` (optional bool ``[B, V]``) is the EXPLICIT mask path
    used by constrained decoding: False entries are forced to ``-inf``
    before top-k/top-p, so the constraint shrinks the candidate set the
    statistical knobs then act on.  An all-True mask is an exact no-op
    (``jnp.where`` returns the untouched lane), preserving bitwise parity
    for unconstrained rows.

    The vocabulary is sorted only if some row carries a live threshold
    (a ``lax.cond`` on the knob arrays), and then once.  ``rows``
    (optional bool ``[B]``) names the rows whose result the caller reads:
    a threshold on any other row (a greedy request that carries
    ``top_p=0.9``) does not count, and such a row's result is unspecified.
    """
    lt = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)[:, None]
    if token_mask is not None:
        lt = jnp.where(token_mask, lt, -jnp.inf)
    k = jnp.asarray(top_k, jnp.int32)
    live = has_threshold(k, top_p, logits.shape[-1])
    if rows is not None:
        live = live & rows
    return jax.lax.cond(jnp.any(live), lambda: _cut(lt, k, top_p), lambda: lt)


def sample_rows(logits, key, do_sample, temperature, top_k, top_p,
                token_mask=None):
    """Per-row token selection: logits ``[B, V]`` -> int32 ids ``[B]``.

    Each row carries its own ``(do_sample, temperature, top_k, top_p)``;
    greedy rows take the raw argmax (no masking touches them), sampled
    rows draw categorically from the masked distribution.  With no
    sampled row the program runs the argmax alone; the scaling, the noise
    and (see :func:`mask_logits`) the sort run only when a row needs them.

    ``token_mask`` (bool ``[B, V]``) constrains BOTH paths: greedy rows
    argmax over the masked logits (a constrained greedy row must emit an
    allowed token), and sampled rows inherit the mask through
    :func:`mask_logits`.  Rows with an all-True mask are untouched.
    """
    greedy_src = logits if token_mask is None else jnp.where(
        token_mask, logits, -jnp.inf)
    greedy = jnp.argmax(greedy_src, axis=-1).astype(jnp.int32)

    def draw():
        masked = mask_logits(logits, temperature, top_k, top_p, token_mask,
                             rows=do_sample)
        sampled = jax.random.categorical(key, masked, axis=-1)
        return jnp.where(do_sample, sampled.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(do_sample), draw, lambda: greedy)


def spec_accept(logits, drafts, key, do_sample, temperature, top_k, top_p):
    """Speculative-decoding accept/rollback decision, fully on device.

    ``logits`` ``[B, K+1, V]`` is the verify pass's scoring ladder: column
    ``i`` is the model's next-token distribution GIVEN the context plus
    the first ``i`` draft tokens (the verify input is
    ``[last_token, draft_0 .. draft_{K-1}]``, so every column conditions
    only on accepted-or-earlier tokens).  ``drafts`` ``[B, K]`` int32.
    Sampling knobs are per-row arrays as in :func:`sample_rows`.

    Returns ``(out [B, K+1] int32, n_accept [B] int32)``: row ``b`` emits
    ``out[b, :n_accept[b] + 1]`` — the accepted draft tokens followed by
    one correction/bonus token — so every verify call advances every row
    by at least one token.  Columns past the emission count are the
    would-have-been tokens of rejected positions; callers ignore them.

    - Greedy rows accept the longest prefix where ``argmax(logits[:, i])
      == drafts[:, i]``; the emitted tokens are the argmax ladder itself,
      hence bitwise-identical to non-speculative greedy decoding.
    - Sampled rows run standard rejection sampling against the drafter's
      ONE-HOT proposal (the n-gram drafter is deterministic): draft ``i``
      is accepted with probability ``p_i(draft_i)`` under the masked
      target distribution; the first rejection resamples from the
      residual (target with the rejected token zeroed, renormalized —
      ``norm(max(p - q, 0))`` for one-hot ``q``), and a fully accepted
      run samples the bonus token from the last column.  The emitted
      token distribution is exactly the non-speculative sampler's.
    """
    B, S, V = logits.shape
    K = S - 1
    # ---- greedy path: longest argmax-matching prefix
    ladder = jnp.argmax(logits, axis=-1).astype(jnp.int32)        # [B, K+1]
    g_match = (ladder[:, :K] == drafts).astype(jnp.int32)
    g_acc = jnp.sum(jnp.cumprod(g_match, axis=-1), axis=-1)       # [B]

    # ---- sampled path: one-hot-q rejection sampling on masked logits
    def sampled():
        flat = mask_logits(
            logits.reshape(B * S, V),
            jnp.repeat(temperature, S), jnp.repeat(top_k, S),
            jnp.repeat(top_p, S), rows=jnp.repeat(do_sample, S))
        masked = flat.reshape(B, S, V)
        p = jax.nn.softmax(masked, axis=-1)
        p_draft = jnp.take_along_axis(
            p[:, :K], drafts[..., None], axis=-1)[..., 0]         # [B, K]
        key_u, key_r = jax.random.split(key)
        u = jax.random.uniform(key_u, (B, K), jnp.float32)
        s_match = (u < p_draft).astype(jnp.int32)
        s_acc = jnp.sum(jnp.cumprod(s_match, axis=-1), axis=-1)   # [B]
        n_acc = jnp.where(do_sample, s_acc, g_acc).astype(jnp.int32)
        # correction/bonus token for sampled rows, drawn at column n_acc:
        # a rejection (n_acc < K) zeroes the rejected draft out of the
        # residual; a clean run (n_acc == K) samples the bonus unmodified
        col = jnp.take_along_axis(masked, n_acc[:, None, None], axis=1)[:, 0]
        rej_draft = jnp.take_along_axis(
            drafts, jnp.clip(n_acc, 0, K - 1)[:, None], axis=-1)[:, 0]
        rejected = n_acc < K
        col = jnp.where(
            rejected[:, None]
            & (jnp.arange(V)[None, :] == rej_draft[:, None]), -jnp.inf, col)
        corr = jax.random.categorical(key_r, col, axis=-1).astype(jnp.int32)
        s_out = jnp.concatenate(
            [drafts, jnp.zeros((B, 1), jnp.int32)], axis=-1)      # [B, K+1]
        s_out = jnp.where(
            jnp.arange(K + 1)[None, :] == n_acc[:, None], corr[:, None],
            s_out)
        return jnp.where(do_sample[:, None], s_out, ladder), n_acc

    # with every row greedy the ladder is the answer: the sampled path
    # (masking, soft-max, noise over [B*(K+1), V]) runs only if a row draws
    out, n_acc = jax.lax.cond(
        jnp.any(do_sample), sampled,
        lambda: (ladder, g_acc.astype(jnp.int32)))
    return out.astype(jnp.int32), n_acc
