"""The fused sampler does only the work its per-row knobs ask for, and what
it does is bitwise what the two-sort sampler did.

The pre-change ``mask_logits`` / ``sample_rows`` / ``spec_accept`` (two sorts
of the vocabulary, every row, every step) are kept HERE as the plain
reference.  Logits are bfloat16 values on a quarter-step grid, so that ties
sit on the k-th value and on the top-p cut-off.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import sampling

B, V = 8, 512


# ------------------------------------------------- the two-sort reference
def ref_mask_logits(logits, temperature, top_k, top_p, token_mask=None):
    V = logits.shape[-1]
    lt = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)[:, None]
    if token_mask is not None:
        lt = jnp.where(token_mask, lt, -jnp.inf)
    k = jnp.asarray(top_k, jnp.int32)
    use_k = (k > 0) & (k < V)
    sorted_lt = jnp.sort(lt, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(
        sorted_lt, jnp.clip(k - 1, 0, V - 1)[:, None], axis=-1)
    lt = jnp.where(use_k[:, None] & (lt < kth), -jnp.inf, lt)
    use_p = top_p < 1.0
    sorted_lt = jnp.sort(lt, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_lt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(sorted_lt, cutoff_idx, axis=-1)
    return jnp.where(use_p[:, None] & (lt < cutoff), -jnp.inf, lt)


def ref_sample_rows(logits, key, do_sample, temperature, top_k, top_p,
                    token_mask=None):
    greedy_src = logits if token_mask is None else jnp.where(
        token_mask, logits, -jnp.inf)
    greedy = jnp.argmax(greedy_src, axis=-1).astype(jnp.int32)
    masked = ref_mask_logits(logits, temperature, top_k, top_p, token_mask)
    sampled = jax.random.categorical(key, masked, axis=-1).astype(jnp.int32)
    return jnp.where(do_sample, sampled, greedy)


def ref_spec_accept(logits, drafts, key, do_sample, temperature, top_k,
                    top_p):
    B, S, V = logits.shape
    K = S - 1
    ladder = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    g_match = (ladder[:, :K] == drafts).astype(jnp.int32)
    g_acc = jnp.sum(jnp.cumprod(g_match, axis=-1), axis=-1)
    flat = ref_mask_logits(
        logits.reshape(B * S, V), jnp.repeat(temperature, S),
        jnp.repeat(top_k, S), jnp.repeat(top_p, S))
    masked = flat.reshape(B, S, V)
    p = jax.nn.softmax(masked, axis=-1)
    p_draft = jnp.take_along_axis(
        p[:, :K], drafts[..., None], axis=-1)[..., 0]
    key_u, key_r = jax.random.split(key)
    u = jax.random.uniform(key_u, (B, K), jnp.float32)
    s_match = (u < p_draft).astype(jnp.int32)
    s_acc = jnp.sum(jnp.cumprod(s_match, axis=-1), axis=-1)
    n_acc = jnp.where(do_sample, s_acc, g_acc).astype(jnp.int32)
    col = jnp.take_along_axis(masked, n_acc[:, None, None], axis=1)[:, 0]
    rej_draft = jnp.take_along_axis(
        drafts, jnp.clip(n_acc, 0, K - 1)[:, None], axis=-1)[:, 0]
    rejected = n_acc < K
    col = jnp.where(
        rejected[:, None] & (jnp.arange(V)[None, :] == rej_draft[:, None]),
        -jnp.inf, col)
    corr = jax.random.categorical(key_r, col, axis=-1).astype(jnp.int32)
    s_out = jnp.concatenate([drafts, jnp.zeros((B, 1), jnp.int32)], axis=-1)
    s_out = jnp.where(
        jnp.arange(K + 1)[None, :] == n_acc[:, None], corr[:, None], s_out)
    out = jnp.where(do_sample[:, None], s_out, ladder)
    return out.astype(jnp.int32), n_acc


# ------------------------------------------------------------- the grid
def _logits(seed, shape=(B, V)):
    """bfloat16 on a quarter-step grid: many exact ties in every row."""
    x = np.random.default_rng(seed).normal(0.0, 2.0, shape)
    return jnp.asarray(np.round(x * 4.0) / 4.0, jnp.bfloat16)


def _rows(value, dtype):
    value = np.asarray(value)
    return jnp.asarray(np.broadcast_to(value, (B,)), dtype)


#: name -> (do_sample, temperature, top_k, top_p, with a token mask)
GRID = {
    "all_greedy": (False, 1.0, 0, 1.0, False),
    "greedy_with_knobs": (False, 0.7, 5, 0.9, False),
    "mixed": ([0, 1, 0, 1, 1, 0, 0, 1], [1, .7, 1, 1.3, .2, 1, 1, 2.],
              [0, 5, 50, 0, V, 1, 3, V - 1],
              [1., .9, .5, .3, 1., 1., .9, .99], False),
    "temperature_only": (True, [.1, .5, .7, 1., 1.3, 2., 5., 1e-9],
                         0, 1.0, False),
    "top_k_1": (True, 0.8, 1, 1.0, False),
    "top_k_small": (True, 0.8, [2, 3, 5, 8, 13, 21, 34, 50], 1.0, False),
    "top_k_V_minus_1": (True, 1.0, V - 1, 1.0, False),
    "top_k_V": (True, 1.0, V, 1.0, False),
    "top_k_0": (True, 1.0, 0, 1.0, False),
    "top_k_over_V": (True, 1.0, V + 7, 1.0, False),
    "top_p_tiny": (True, 1.0, 0, 0.01, False),
    "top_p_0.9": (True, 1.0, 0, 0.9, False),
    "top_p_1": (True, 1.5, 0, 1.0, False),
    "top_p_sweep": (True, 0.9, 0,
                    [.01, .1, .25, .5, .75, .9, .99, 1.], False),
    "both": (True, [.5, .7, 1., 1., 1.3, 2., .3, 1.],
             [1, 5, 50, V - 1, V, 0, 7, 200],
             [.9, .5, .99, .3, .01, .75, 1., .6], False),
    "token_mask": (True, 0.9, [0, 5, 50, 0, V, 1, 3, V - 1],
                   [1., .9, .5, .3, 1., 1., .9, .99], True),
    "token_mask_mixed": ([1, 0, 1, 0, 0, 1, 1, 0], 1.0,
                         [0, 5, 0, 0, 4, 0, 3, 0],
                         [1., 1., .8, 1., .9, 1., 1., .2], True),
    "token_mask_greedy": (False, 1.0, 0, 1.0, True),
    # one sampling row without a threshold beside greedy rows that carry
    # one: the thresholds of rows nobody draws for must not engage the sort
    "greedy_rows_carry_top_p": ([0, 0, 1, 0, 0, 0, 0, 0], 0.9,
                                [0, 5, 0, 0, 0, 0, 0, 0],
                                [.9, .9, 1., .5, .9, .9, .9, .9], False),
}


def _case(name, seed=0):
    do_s, temp, k, p, with_mask = GRID[name]
    mask = None
    if with_mask:
        m = np.random.default_rng(seed + 99).random((B, V)) < 0.4
        m[:, 0] = True     # never an empty row
        m[-1] = True       # an unconstrained row beside the others
        mask = jnp.asarray(m)
    return (_logits(seed), _rows(do_s, bool), _rows(temp, jnp.float32),
            _rows(k, jnp.int32), _rows(p, jnp.float32), mask)


def _equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("name", sorted(GRID))
def test_masked_logits_are_bitwise_the_two_sort_reference(name):
    logits, _, temp, k, p, mask = _case(name)
    _equal(jax.jit(sampling.mask_logits)(logits, temp, k, p, mask),
           jax.jit(ref_mask_logits)(logits, temp, k, p, mask))


@pytest.mark.parametrize("name", sorted(GRID))
def test_drawn_tokens_are_the_reference_draws_under_the_same_key(name):
    logits, do_s, temp, k, p, mask = _case(name, seed=1)
    new, ref = jax.jit(sampling.sample_rows), jax.jit(ref_sample_rows)
    for s in range(3):
        key = jax.random.PRNGKey(1000 + s)
        _equal(new(logits, key, do_s, temp, k, p, mask),
               ref(logits, key, do_s, temp, k, p, mask))


@pytest.mark.parametrize(
    "name", sorted(n for n in GRID if not GRID[n][4]))
def test_spec_accept_is_the_reference_decision_under_the_same_key(name):
    _, do_s, temp, k, p, _ = _case(name)
    K = 3
    logits = _logits(2, (B, K + 1, V))
    # drafts that agree with the verifier at some positions, so prefixes
    # of every length are accepted somewhere
    ladder = np.asarray(jnp.argmax(logits, axis=-1))[:, :K]
    rnd = np.random.default_rng(3).integers(0, V, (B, K))
    keep = np.random.default_rng(4).random((B, K)) < 0.6
    drafts = jnp.asarray(np.where(keep, ladder, rnd), jnp.int32)
    new, ref = jax.jit(sampling.spec_accept), jax.jit(ref_spec_accept)
    for s in range(2):
        key = jax.random.PRNGKey(2000 + s)
        out, n = new(logits, drafts, key, do_s, temp, k, p)
        r_out, r_n = ref(logits, drafts, key, do_s, temp, k, p)
        _equal(n, r_n)
        _equal(out, r_out)


def test_ties_sit_on_both_thresholds():
    """The grid means what it says: in the tie cases the k-th value and the
    top-p cut-off are each shared by several entries of a row, and the
    value rule keeps all of them."""
    logits, _, temp, k, p, _ = _case("both")
    lt = np.asarray(logits, np.float32) / np.asarray(temp)[:, None]
    out = np.asarray(sampling.mask_logits(logits, temp, k, p))
    tied_k = tied_p = 0
    for b in range(B):
        kept = np.sort(lt[b][np.isfinite(out[b])])
        tied_p += int((lt[b] == kept[0]).sum() > 1)
        if 0 < int(k[b]) < V:
            kth = np.sort(lt[b])[::-1][int(k[b]) - 1]
            tied_k += int((lt[b] == kth).sum() > 1)
            assert np.isfinite(out[b]).sum() <= (lt[b] >= kth).sum()
    assert tied_k >= 2 and tied_p >= 2


# ------------------------------------------- what the lowered text holds
def vocab_sorts(lowered, vocab):
    """(outside, inside): the `stablehlo.sort`s over an axis of ``vocab``
    that a lowered program runs whatever its input says, and those it runs
    only inside a branch of a `stablehlo.case`.  jax outlines `jnp.sort`
    into a private function, so the walk starts at `main` and follows every
    `func.call` with what it knows of the caller."""
    module = lowered.compiler_ir()
    funcs = {op.attributes["sym_name"].value: op.operation
             for op in module.body.operations
             if op.operation.name == "func.func"}
    counts = {False: 0, True: 0}

    def walk(op, under_case):
        for region in op.regions:
            for block in region.blocks:
                for child in block.operations:
                    child = child.operation
                    if child.name == "stablehlo.sort" and \
                            vocab in child.operands[0].type.shape:
                        counts[under_case] += 1
                    if child.name in ("func.call", "call"):
                        walk(funcs[child.attributes["callee"].value],
                             under_case)
                    walk(child, under_case or child.name == "stablehlo.case")

    walk(funcs["main"], False)
    return counts[False], counts[True]


def test_one_sort_inside_a_conditional_where_there_were_two_outside():
    logits, do_s, temp, k, p, _ = _case("both")
    key = jax.random.PRNGKey(0)

    def sorts(fn, *args):
        return vocab_sorts(jax.jit(fn).lower(*args), V)

    assert sorts(ref_sample_rows, logits, key, do_s, temp, k, p) == (2, 0)
    assert sorts(sampling.sample_rows, logits, key, do_s, temp, k, p) == (0, 1)
    assert sorts(sampling.mask_logits, logits, temp, k, p) == (0, 1)
    ladder, drafts = _logits(2, (B, 4, V)), jnp.zeros((B, 3), jnp.int32)
    assert sorts(ref_spec_accept, ladder, drafts, key, do_s, temp, k, p) \
        == (2, 0)
    assert sorts(sampling.spec_accept, ladder, drafts, key, do_s, temp, k, p) \
        == (0, 1)


class _Recorded:
    """A compiled engine program that remembers the shapes it was called
    with (the caches are donated: the arrays themselves are gone after)."""

    def __init__(self, jit):
        self.jit, self.avals = jit, None

    def __call__(self, *args):
        self.avals = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), args)
        return self.jit(*args)


@pytest.mark.parametrize("kind", ["paged", "verify-paged"])
def test_the_engines_programs_sort_the_vocabulary_once_under_a_branch(kind):
    """On the lowered text of a tiny engine's `llm_decode` (and of its
    speculative verify): no sort over the vocabulary runs unconditionally,
    and the branch that needs one holds one, not two."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(7)
    cfg = LlamaConfig.tiny(tensor_parallel=False, use_flash_attention=False,
                           max_position_embeddings=256)
    model = LlamaForCausalLM(cfg)
    model.eval()
    kw = dict(kv_layout="paged", page_size=32, prefill_chunk=16)
    if kind.startswith("verify"):
        eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128, spec_k=2,
                        **kw)
        rec = eng._verify_jit = _Recorded(eng._get_verify())
    else:
        eng = LLMEngine(model, max_batch_slots=2, max_seq_len=128, **kw)
        rec = eng._decode_jit[1] = _Recorded(eng._get_decode(1))
    prompt = np.arange(3, 13, dtype=np.int32)
    assert len(eng.generate(prompt, max_new_tokens=4)) == 4
    lowered = rec.jit.lower(*rec.avals)
    assert vocab_sorts(lowered, cfg.vocab_size) == (0, 1)
    if not kind.startswith("verify"):
        # both conditionals, and the sort under them, keep the named scope
        # the profiler's op names (and PERF.md's sums by scope) go by
        assert re.search(r"sampler/cond/branch_1_fun/cond/branch_1_fun/"
                         r"jit\(sort\)", lowered.as_text(debug_info=True))
