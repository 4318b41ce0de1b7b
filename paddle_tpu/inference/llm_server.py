"""Continuous-batching LLM serving engine (slot-based, vLLM-style).

Reference gap: the v2.3-era AnalysisPredictor serves one fixed-shape model
program per request (analysis_predictor.h) — there is no decode server.
This engine is the TPU-native design the kv-cache stack invites:

- a FIXED pool of batch slots over a PAGED kv cache (the Ragged Paged
  Attention design, models/kv_cache.py paged contract): one global page
  pool a layer plus per-slot page tables, so capacity follows the actual
  sequence lengths, not slots x max_seq_len reserved rows;
- ONE compiled decode step for the whole pool per token: each slot carries
  its own position, so the rope offsets, cache scatters and the Pallas
  decode-attention masks are all per-slot vectors — requests at different
  depths decode together with no recompilation and no padding restarts;
- admission by CHUNKED PREFILL into a free slot, gated by FREE PAGES:
  prompts prefill in fixed-size chunks interleaved with decode ticks
  through ONE compiled chunk program (no compile per prompt length), so a
  long prompt never stalls running slots for more than one chunk step,
  and the request joins the next decode tick after its final chunk;
- completion by eos/max-tokens frees the slot and its pages for the next
  queued request; when the pool runs dry an in-flight request is preempted
  recompute-style (requeued with what it generated so far).

``warmup()`` pre-compiles the programs so the first request pays no
compile latency.  There is one engine: ``kv_layout`` is accepted for the
callers that still pass ``"paged"`` and selects nothing.  The dense
per-slot layout this file once also held is gone; the dense STATIC cache
of ``models.generation.generate()`` is the reference the parity tests
hold this engine to.

On top of the page pool sits the PREFIX CACHE (on by default,
``prefix_cache=False`` to disable): a radix index over chained hashes of
page-aligned prompt blocks (inference/prefix_cache.py) remembers which
pages hold which prefixes.  Admission maps the cached pages straight into
the new slot's page table — pages are REFCOUNTED, so finish/expiry/preempt
decref instead of freeing — charges the pool only for the UNIQUE
(uncached) pages, and starts chunked prefill at the first uncached token.
A slot that must write into a shared partially-filled tail page forks it
copy-on-write first; unreferenced cached prefixes are LRU-evicted when the
free list runs dry.  Greedy outputs are bitwise identical with the cache
on or off: shared pages hold exactly the kv the slot would have computed
itself (causal attention — a token's kv never depends on what follows it).

``spec_k > 0`` turns on SPECULATIVE decoding: a host-side drafter
(prompt-lookup n-gram by default, or a small draft model —
models/spec_decode.py) proposes K tokens per slot per tick and ONE
compiled verify pass scores all K+1 positions through the same
paged cache path, emitting the longest valid prefix plus a
correction token — up to (K+1)x fewer serial model passes at identical
greedy output.  Rollback rides the existing machinery: the slot position
stops at the accept point, rejected rows are overwritten before any read,
and pages past the accept point decref back to the pool each tick.

The pump keeps ONE DECODE PROGRAM IN FLIGHT: a tick dispatches its decode
program before it reads the previous tick's result, whose tokens feed it on
the device, and books that result (emit, stamp, finish) while the device
runs the new one — the host's work hides under the device's.  Where the
next program needs what the host has not read (a constrained row, a
drafter, a preemption) the tick reads first and is synchronous
(`_decode_tick`; `stats()["tick_pipeline"]`).  Tokens are the synchronous
pump's, token for token.  For a model with ``mixed_step`` a tick that
carries a prefill chunk dispatches ONE program, ``llm_mixed``: the chunk's
rows and the decode rows through one pass over the weights, and a final
chunk's first token is read where that program is booked, a tick later
(`_mixed_program`, `_first_token`).

The engine is deterministic and thread-free by default (`step()` pumps one
tick, after which a decode result is as a rule still in flight;
`run_until_complete()` drains, leaving none); `start()` spawns the
background pump for server use.

Numerics: per-request outputs are exactly the solo `generate()` tokens in
f32 (verified on TPU under staggered admission).  In bf16, greedy argmax
can flip on near-tied logits when a slot is co-batched with others (batch
shape changes the reduction order) — inherent to reduced precision in any
batched server, not a positional error.
"""
from __future__ import annotations

import math
import os
import queue
import threading
import time
from concurrent.futures import Future
from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from ..autograd import tape
from ..framework import random as _fr
from ..ops import lora as _oplora
from ..observability import flight_recorder as _flight
from ..observability import goodput as _goodput
from ..observability import metrics as _obs
from ..observability import profiling as _profiling
from ..observability import slo as _slo
from ..observability import tracing as _tracing
from ..observability.spans import PhaseClock as _PhaseClock
from ..observability.spans import span as _span
from ..ops.sampling import has_threshold as _has_threshold
from ..ops.sampling import sample_rows as _sample_rows
from ..ops.sampling import spec_accept as _spec_accept
from ..models.kv_cache import SlotRows as _SlotRows
from ..models.kv_cache import LatentPaged as _LatentPaged
from ..models.kv_cache import SparsePaged as _SparsePaged
from ..tensor.tensor import Tensor
from . import constrain as _constrain

__all__ = ["LLMEngine", "ServerOverloadedError", "DeadlineExceededError"]

# Serving telemetry (README §Observability): queue depth + shed/expiry rates
# are the queue-collapse signals; TTFT and decode tok/s are the user-visible
# latency/throughput pair (the Gemma-on-TPU serving comparison's axes).
_M_QUEUE_DEPTH = _obs.gauge(
    "llm_queue_depth", "Requests waiting in the admission queue")
_M_ACTIVE_SLOTS = _obs.gauge(
    "llm_active_slots", "Batch slots decoding this tick")
_M_SUBMITTED = _obs.counter(
    "llm_requests_submitted_total", "Requests accepted into the queue")
_M_SHED = _obs.counter(
    "llm_requests_shed_total",
    "Requests rejected at admission (queue full / maintenance mode)")
_M_ADMITTED = _obs.counter(
    "llm_admissions_total", "Requests admitted into a batch slot (prefill)")
_M_COMPLETED = _obs.counter(
    "llm_requests_completed_total", "Requests finished with a result")
_M_EXPIRED = _obs.counter(
    "llm_deadline_expiries_total",
    "Requests failed at their deadline", labelnames=("where",))
_M_QUEUE_WAIT = _obs.histogram(
    "llm_queue_wait_seconds", "Time from submit to slot admission")
_M_TTFT = _obs.histogram(
    "llm_ttft_seconds",
    "Time to first token (submit -> prefill's first generated token)")
_M_E2E = _obs.histogram(
    "llm_request_duration_seconds", "End-to-end request latency")
_M_DECODE_TOKENS = _obs.counter(
    "llm_decode_tokens_total", "Tokens emitted by decode ticks")
_M_DECODE_TPS = _obs.gauge(
    "llm_decode_tokens_per_second",
    "Aggregate decode throughput of the latest tick")
_M_TICK_SECONDS = _obs.histogram(
    "llm_decode_tick_duration_seconds",
    "One engine tick (admissions + compiled decode + bookkeeping)")
_M_WATCHDOG = _obs.counter(
    "llm_pump_watchdog_trips_total",
    "Background pump deaths caught by the watchdog")
_M_PREFILL_CHUNKS = _obs.counter(
    "llm_prefill_chunks_total",
    "Prefill chunks executed (chunked, decode-interleaved admission)")
_M_PREFILL_CHUNK_S = _obs.histogram(
    "llm_prefill_chunk_seconds", "One compiled prefill-chunk call")
_M_PAGES_IN_USE = _obs.gauge(
    "llm_kv_pages_in_use_count",
    "KV-cache pages currently allocated to slots")
_M_PAGE_UTIL = _obs.gauge(
    "llm_kv_page_utilization_ratio",
    "Allocated fraction of the allocatable kv page pool")
_M_PAGE_PREEMPT = _obs.counter(
    "llm_page_preemptions_total",
    "In-flight requests preempted because the kv page pool ran dry")
_M_WARMUP_S = _obs.gauge(
    "llm_warmup_compile_seconds",
    "Wall time of the last warmup() precompile pass")
_M_PREFIX_HIT_RATIO = _obs.gauge(
    "llm_prefix_cache_hit_ratio",
    "Cumulative fraction of prompt tokens served from the prefix cache")
_M_PAGES_SHARED = _obs.gauge(
    "llm_kv_pages_shared_count",
    "KV pages currently mapped by more than one holder (slots/prefix cache)")
_M_COW = _obs.counter(
    "llm_cow_copies_total",
    "Copy-on-write forks: a slot wrote into a shared kv page")
_M_PREFIX_EVICT = _obs.counter(
    "llm_prefix_evictions_total",
    "Cached prefix pages reclaimed (LRU eviction / tail steal-back)")
_M_SPEC_DRAFTED = _obs.counter(
    "llm_spec_drafted_tokens_total",
    "Draft tokens proposed to speculative verify steps")
_M_SPEC_ACCEPTED = _obs.counter(
    "llm_spec_accepted_tokens_total",
    "Draft tokens accepted by speculative verify steps")
_M_SPEC_ROLLED_BACK = _obs.counter(
    "llm_spec_rolled_back_tokens_total",
    "Draft tokens rejected and rolled back by speculative verify steps")
_M_SPEC_RB_PAGES = _obs.counter(
    "llm_spec_rolled_back_pages_total",
    "KV pages reclaimed by speculative rollback trims")
_M_SPEC_ACCEPT_RATIO = _obs.gauge(
    "llm_spec_acceptance_ratio",
    "Cumulative accepted/drafted fraction of speculative decoding")
_M_SPEC_VERIFY_S = _obs.histogram(
    "llm_spec_verify_seconds",
    "One compiled speculative verify call (K+1 positions per slot)")
_M_RECOMPUTE_TOKENS = _obs.counter(
    "llm_recompute_tokens_total",
    "Prompt+prefix tokens re-prefilled after a requeue (page-pool-dry "
    "or mid-verify preemption, COW-starved prefill) — the token cost of "
    "preemption, feeding the goodput ledger's preempt_recomputed class",
    labelnames=("reason",))
_M_ADM_REORDERS = _obs.counter(
    "llm_admission_reorders_total",
    "Cache-aware admissions that bypassed the FIFO queue head")
_M_DRAINING = _obs.gauge(
    "llm_draining_value",
    "1 while the engine is draining (admission closed, in-flight finishing)")
_M_DRAIN_EXPIRED = _obs.counter(
    "llm_drain_expired_total",
    "Requests failed with DeadlineExceededError because a bounded drain "
    "(drain(deadline_s=)) expired with them still queued or in flight")
_M_TIER_HITS = _obs.counter(
    "llm_prefix_tier_hits_total",
    "Prompt tokens served per cache tier at admission (hbm = radix pages "
    "already resident; host/disk = pages promoted from a lower tier)",
    labelnames=("tier",))
_M_KV_DEMOTIONS = _obs.counter(
    "llm_kv_demotions_total",
    "Cached prefix pages staged device->host by the demotion worker")
_M_KV_PROMOTIONS = _obs.counter(
    "llm_kv_promotions_total",
    "Staged prefix pages uploaded host->device at admission")
_M_KV_HOST_BYTES = _obs.gauge(
    "llm_kv_host_pool_bytes",
    "Bytes of kv pages currently staged in the host-RAM tier")
_M_KV_PROMOTE_S = _obs.histogram(
    "llm_kv_promote_seconds",
    "One batched promotion (tier reads + a single host->device upload)")

_M_MOE_PAIRS = _obs.counter(
    "llm_moe_pairs_total",
    "(token, expert) pairs routed by the expert layers of the compiled "
    "serving programs, by whether this device holds the expert and by "
    "program (real rows only)", labelnames=("where", "program"))
_M_MOE_TOUCHED = _obs.counter(
    "llm_moe_experts_touched_total",
    "Held experts that had at least one pair, summed over expert-layer calls",
    labelnames=("program",))
_M_MOE_CALLS = _obs.counter(
    "llm_moe_layer_calls_total",
    "Expert-layer calls (one a layer a decode step or prefill chunk)",
    labelnames=("program",))
_M_MOE_MAX_LOAD = _obs.gauge(
    "llm_moe_max_expert_load_count",
    "Most pairs one held expert got in a layer of the latest decode tick")
_MOE_PROGRAMS = ("decode", "prefill")  # the planes of the device accumulator
#: what a block-sparse attention layer reports a call (CacheKind.compressed)
_SPARSE_FIELDS = ("layer_calls", "context_blocks", "selected_blocks")
_M_SPARSE_SELECTED = _obs.counter(
    "llm_sparse_blocks_selected_total",
    "Key blocks the block-sparse attention layers selected, summed over "
    "their query calls (one a real row a layer)", labelnames=("program",))
#: what a latent attention layer reports a call ("paged_latent")
_LATENT_FIELDS = ("layer_calls", "context_tokens")
_M_LATENT_CONTEXT = _obs.counter(
    "llm_latent_context_tokens_total",
    "Context tokens the latent attention layers' queries attended, summed "
    "over their query calls (one a real row a layer)", labelnames=("program",))
_M_STATE_CKPT = _obs.counter(
    "llm_state_checkpoints_total",
    "Recurrent-state checkpoints at a cached prefix's end: stored after a "
    "prefill, restored into a slot by a prefix hit, evicted with their node "
    "or for a newer prefix", labelnames=("event",))
_CKPT_EVENTS = ("stored", "restored", "evicted")
_M_STATE_CARRIED = _obs.counter(
    "llm_state_carried_chunks_total",
    "Prefill chunks of a model with recurrent state that start from the "
    "state the same prompt's previous chunk left, not from zeros or a "
    "checkpoint (counted where the chunk is staged)")

#: The pump's tick, cut into mutually exclusive phases in tick order (a
#: speculative tick runs the spec_* phases in place of decode_*).  Every
#: second between the tick's first line and its last is in exactly one.
_TICK_PHASES = (
    "expire",            # _expire_queued, _expire_slots
    "admit",             # _start_prefill: pop, prefix match, tiers, pages
    "prefill_stage",     # the chunk's checks, COW fork and host arrays
    "prefill_dispatch",  # the (asynchronous) call of the program that
                         # carries a chunk: llm_prefill_chunk, or llm_mixed
                         # with the tick's decode rows beside it
    "first_token_sync",  # np.asarray(logits) + host select + activation
    "decode_stage",      # page growth, uploads, rng split, knobs, masks
    "decode_dispatch",   # the decode program's call, until it returns
    "decode_sync",       # any read of a decode result in flight: as a rule
                         # the PREVIOUS program's, while this tick's runs
    "bookkeep",          # gauges, token loop, _finish, span flushes
    "spec_draft", "spec_stage", "spec_dispatch", "spec_sync", "spec_accept")
#: the phases the goodput ledger's productive decode seconds are made of
_DECODE_PHASES = ("decode_stage", "decode_dispatch", "decode_sync")
#: phases in which the host only waits for the device; every other phase
#: is the host's own work (stats()["tick_phases"]["host_s"])
_SYNC_PHASES = ("first_token_sync", "decode_sync", "spec_sync")
_M_TICK_PHASE_S = _obs.counter(
    "llm_tick_phase_seconds_total",
    "Pump seconds per exclusive tick phase (the phases add up to "
    "llm_decode_tick_duration_seconds' sum)", labelnames=("phase",))
_TICK_PHASE_SERIES = {p: _M_TICK_PHASE_S.labels(phase=p)
                      for p in _TICK_PHASES}
#: why the pump read a decode result with no later program queued behind it
#: (_decode_tick): what the next program needs of the host, or nothing to run
_DRAIN_REASONS = ("constrained", "spec", "preempt", "idle", "stop", "warmup")
_M_TICK_PIPELINE = _obs.counter(
    "llm_tick_pipeline_ticks_total",
    "Decode results the pump read, by whether the next decode program was "
    "already dispatched (overlapped) or why it was not (the drain's "
    "reason); mode=mixed: programs that carried a prefill chunk AND decode "
    "rows through one weight pass",
    labelnames=("mode",))
_PIPELINE_SERIES = {m: _M_TICK_PIPELINE.labels(mode=m)
                    for m in ("overlapped", "mixed") + _DRAIN_REASONS}
#: why a tick left the queue head waiting: exactly one reason a tick
_BLOCK_REASONS = ("prefill_busy", "no_slot", "no_pages", "no_adapter_page")
_M_ADM_BLOCKED = _obs.counter(
    "llm_admission_blocked_ticks_total",
    "Ticks that ended with a request queued and none admitted, by what "
    "held the queue head", labelnames=("reason",))
_ADM_BLOCKED_SERIES = {r: _M_ADM_BLOCKED.labels(reason=r)
                       for r in _BLOCK_REASONS}
#: how much of the fused sampler a tick's knobs make the device run: the
#: argmax alone, a draw without a sort, or the one sort of the vocabulary
_SAMPLER_PATHS = ("greedy", "draw", "threshold")
_M_DEVICE_SECONDS = _obs.gauge(
    "llm_device_seconds",
    "Device self seconds of the last profile_device() window by compiled "
    "program and first named-scope component (unscoped, unmatched: no "
    "scope, no census row)",
    labelnames=("program", "scope"))
_M_SAMPLER_TICKS = _obs.counter(
    "llm_sampler_ticks_total",
    "Decode and verify ticks by the part of the sampler their knobs "
    "engage (the host's count of the predicate the program branches on)",
    labelnames=("path",))
_SAMPLER_SERIES = {p: _M_SAMPLER_TICKS.labels(path=p)
                   for p in _SAMPLER_PATHS}


def _attn_dispatch_series():
    """[(label values, count)] for every `llm_attn_kernel_total` child.
    The family is declared in ops/decode_attention.py (the dispatchers own
    the trace-time counting); read it through the registry so stats() and
    /metrics agree even if this module loaded first."""
    fam = _obs.REGISTRY.get("llm_attn_kernel_total")
    return [(labels, child.value) for labels, child in fam.series()] \
        if fam is not None else []

#: LLMEngine(slo_targets={...}) keys -> SLO series names (observability.slo
#: sliding-window percentiles + burn rates, README §Observability).
_SLO_SERIES = {"ttft": "llm_ttft", "e2e": "llm_e2e",
               "queue_wait": "llm_queue_wait", "tick": "llm_tick",
               "verify": "llm_verify", "promote": "llm_promote"}

#: Decode ticks coalesce into ONE trace summary span per this many tokens
#: (= ticks at one token a tick; and per admission episode) — a 10k-token
#: decode contributes a bounded handful of spans to its request trace,
#: never 10k.
_DECODE_SPAN_TICKS = 256


def _trace_kv(req):
    """``{"trace_id": ...}`` for flight-recorder correlation, or ``{}``
    when tracing is off (the NULL trace's id is empty)."""
    tid = req.trace.trace_id
    return {"trace_id": tid} if tid else {}


class ServerOverloadedError(RuntimeError):
    """Admission queue full: the request was rejected (load shedding) rather
    than queued without bound.  Callers should retry with backoff."""


class DeadlineExceededError(TimeoutError):
    """The request's deadline elapsed (in the queue or mid-decode); its slot
    was freed for other traffic."""


def _fail_future(fut, exc):
    """set_exception tolerant of a caller cancelling concurrently — a racy
    cancel() between a done() check and set_exception must not blow up the
    pump thread (InvalidStateError) and take the whole engine down."""
    try:
        if not fut.done():
            fut.set_exception(exc)
    except Exception:
        pass  # already cancelled/completed by the caller


def _complete_future(fut, result):
    try:
        if not fut.done():
            fut.set_result(result)
    except Exception:
        pass  # already cancelled/completed by the caller


@dataclass
class _Request:
    prompt: np.ndarray
    max_new_tokens: int
    future: Future
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    deadline: float | None = None
    slot: int = -1
    regrown: int = 0          # generated tokens a preemption has already
                              # appended to ``prompt`` (the next one appends
                              # only what came after them)
    skip_cache: bool = False  # set on preemption: re-admission goes fully
                              # private so a COW-starved request can never
                              # re-match the same contended pages forever
    match_epoch: int = -1     # memoized radix match for a head-of-line
    match_result: tuple | None = None  # request spinning on a full pool
    hit_tokens: int = 0       # cache hit credited at first admission —
                              # reversed if a COW-starved requeue abandons
                              # the prefill those tokens were skipping
    tier_hit_tokens: int = 0  # of those, tokens PROMOTED from the host or
                              # disk tier (hbm attribution = hit - these)
    prefill_from: int = 0     # the prompt token this admission's prefill
                              # starts at (a prefix hit's end, else 0)
    tokens: list = field(default_factory=list)
    submit_ts: float | None = None  # engine-clock stamps for the latency
    admit_ts: float | None = None   # histograms (queue wait / TTFT / e2e)
    # ---- request-scoped tracing (observability.tracing): the trace IS
    # the explicit context object — it rides on the request, never in a
    # thread-local the jitted paths could see
    trace: object = _tracing.NULL_TRACE
    adm_span: object = None         # open "admission" span handle, held
                                    # across prefill-chunk ticks
    adm_episode: int = 0            # admission attempts (requeues re-admit)
    requeue_reason: str | None = None  # why the LAST requeue happened —
                                    # stamped on the next admission span
    token_ts: list = field(default_factory=list)  # perf_counter stamp of
                                    # every emitted token (tokens of one
                                    # tick share one); survives requeues
    dec_i0: int = 0                 # token_ts index where the current
                                    # coalesced decode-summary window begins
    adm_skips: int = 0              # cache-aware admission passed this
                                    # request over (aging/fairness cap)
    spec_drafted: int = 0           # speculative-decode window counters,
    spec_accepted: int = 0          # flushed into the coalesced trace
    spec_draft_s: float = 0.0       # spans alongside the decode summary
    spec_verify_s: float = 0.0
    on_admit: object = None         # fired once at first slot admission —
                                    # the router's admission ack (after it,
                                    # the request is no longer retry-safe)
    on_token: object = None         # cb(index, token, t) per emitted token
    adapter_id: object = None       # LoRA adapter id (None = base model)
    adapter_page: int = 0           # pool page pinned while in a slot;
                                    # 0 = none held (page 0 is the zero
                                    # adapter, never refcounted)
    constraint: object = None       # compiled TokenConstraint (shared,
                                    # immutable automaton tables)
    cursor: object = None           # per-request automaton cursor; its
                                    # state SURVIVES preemption requeues
                                    # (the regrown prompt's generated tail
                                    # was already consumed token by token)


@dataclass
class _InFlight:
    """A dispatched decode program whose result the host has not read."""
    out: object    # its first result on the device: the tokens [B, eff],
                   # flat with the layers' counts behind them where
                   # the engine keeps accumulators
    eff: int       # tokens a row
    rows: list     # [(slot, request)] of the rows it carried
    moe: object    # the (pairs, layer calls) dispatched since the program
                   # before it, this one included: what its counts cover
    first: object = None  # (request, slot, logits): the final chunk the
                   # program carried (llm_mixed); its first token is
                   # selected where the program is booked


@dataclass
class _Chunk:
    """The prefill chunk a tick has checked and staged: the next ``m``
    prompt tokens of ``req`` (``done`` are in its pages already) for
    ``slot``, and what the program that carries it takes for it."""
    req: _Request
    slot: int
    done: int
    m: int
    args: tuple  # (page-table row [1, M], ids [1, C], done [1], m - 1)

    @property
    def final(self):
        return self.done + self.m >= self.req.prompt.size


def _select_rows(logits, key, do_sample, temperature, top_k, top_p,
                 token_mask=None):
    """Vectorized per-ROW token selection: each slot carries its own
    (do_sample, temperature, top_k, top_p) — the serving face of the
    fused sampler (ops/sampling.sample_rows), which generation._select
    also delegates to, so the engine and the solo loop share one masking
    + categorical implementation.  ``token_mask`` (bool [B, V]) is the
    constrained-decoding path; all-True rows are exact no-ops.

    What a step pays follows the knob ARRAYS, decided on the device inside
    this one program (``lax.cond``): with every row greedy the argmax alone
    runs; rows that draw without a live top_k / top_p add the scaling and
    the noise; only a threshold on a row that draws sorts the vocabulary,
    and then once — the entries under the k-th value are a suffix of that
    sort, so it is already the sort of the top-k survivors that top-p sums
    over, and the draws are bitwise those of sorting twice.  Nothing is
    compiled when the first sampled request arrives after warmup().  The
    named scope only labels the ops ("sampler" in the profiler's op_name);
    both branches keep it."""
    with jax.named_scope("sampler"):
        return _sample_rows(logits, key, do_sample, temperature, top_k,
                            top_p, token_mask=token_mask)


def _lora_ctx(pool, tree, rows):
    """LoRA epilogue activation for the compiled serving programs' trace:
    a no-op when the engine has no adapter pool (``pool`` carries only the
    static site layout; the traced weights ride in ``tree``/``rows``)."""
    if pool is None:
        return nullcontext()
    return _oplora.activate(pool.site_pools(tree), rows)


def _to_model_caches(kinds, caches, pos, page_tbl, slot_rows=None):
    """The per-layer caches a model's step takes, from what the engine keeps.
    Engine-side entries hold only what persists (the page POOLS (k, v[, ks,
    vs]), a recurrent layer's per-slot state, nothing); pos, the page table
    and the batch's rows are threaded in here, so the donated pytree never
    aliases the shared table once a layer.  ``kinds`` is the model's
    ``cache_kinds()``, or None for a model whose every layer pages k/v."""
    def paged(c):
        return (Tensor(c[0]), Tensor(c[1]), pos, Tensor(page_tbl)) \
            + tuple(Tensor(x) for x in c[2:])

    if kinds is None:
        return [paged(c) for c in caches]
    # a state layer that rotates by position reads it from the rows
    if slot_rows is not None:
        slot_rows = slot_rows._replace(pos=jnp.broadcast_to(
            jnp.asarray(pos, jnp.int32), slot_rows.n_valid.shape))
    return [_SparsePaged(*c, pos, page_tbl, slot_rows) if k.compressed
            else _LatentPaged(c[0], pos, page_tbl, slot_rows)
            if k.kind == "paged_latent"
            else paged(c) if k.kind == "paged_kv"
            else tuple(c) + (slot_rows,) if k.kind == "recurrent"
            else slot_rows
            for k, c in zip(kinds, caches)]


def _from_model_caches(kinds, new_caches):
    """Back again: (engine-side caches, what the layers reported: the expert
    layers' counts stacked, then the block-sparse layers' summed, then the
    latent layers' summed — the order of the engine's device accumulators,
    each present only with its layers).  A paged_kv or recurrent layer with
    experts hands its expert counts LAST, after what it keeps."""
    raw, moe, sparse, latent = [], [], [], []
    for i, c in enumerate(new_caches):
        kind = "paged_kv" if kinds is None else kinds[i].kind
        if kind in ("paged_kv", "recurrent") and kinds is not None \
                and kinds[i].experts_held:
            moe.append(c[-1])
            c = tuple(c[:-1])
        if kind == "paged_kv" and kinds is not None and kinds[i].compressed:
            raw.append(tuple(c[:3]))
            sparse.append(c[3])
        elif kind == "paged_kv":
            vals = tuple(x._value if isinstance(x, Tensor) else x for x in c)
            raw.append((vals[0], vals[1]) + vals[4:])
        elif kind == "paged_latent":
            # (pool, its counts[, the expert feed-forward's])
            raw.append((c[0],))
            latent.append(c[1])
            if kinds[i].experts_held:
                moe.append(c[2])
        elif kind == "recurrent":
            raw.append(tuple(c))
        else:
            raw.append(())
            if kinds[i].experts_held:
                moe.append(c)
    return raw, ([jnp.stack(moe)] if moe else []) \
        + ([sum(sparse)] if sparse else []) + ([sum(latent)] if latent else [])


def _mixed_program(model, kinds):
    """``llm_mixed``: the compiled step of a tick that carries a prefill
    chunk, for a model with ``mixed_step`` — the chunk's [1, C] rows and
    every slot's decode row through ONE pass over the weights, where
    llm_prefill_chunk and llm_decode would each stream them.  Its arguments
    are llm_decode's at one token a row (the token feed, the keys derived
    from ``(base_key, offset)`` exactly as there, so a row draws the same
    token whichever program carries it) and then the chunk's: its slot's
    page-table row, its ids, the tokens already prefilled, the index of
    its last real token.  The tick masks the chunk's slot out of
    ``page_tbl`` like any slot between chunks; with no row decoding every
    row of it is masked and the program is the chunk's alone.  Results:
    the rows' tokens [B, 1], the caches, the chunk's logits [1, 1, V] at
    ``last_index``, and LAST the token feed of the next program."""
    def llm_mixed(params, buffers, caches, page_tbl, tokens, feed, from_host,
                  pos, do_sample, temperature, top_k, top_p, token_mask,
                  base_key, offset, page_row, ids, off, last_index):
        with jax.named_scope("sampler"):
            key = jax.random.split(jax.random.fold_in(base_key, offset), 1)[0]
        with jax.named_scope("token_feed"):
            tokens = jnp.where(from_host, tokens[:, 0], feed)[:, None]
        restore = model.bind_functional_state(params, buffers)
        try:
            with tape.no_grad():
                logits, first, new_caches = model.mixed_step(
                    Tensor(ids), Tensor(tokens),
                    _to_model_caches(kinds, caches, pos, page_tbl),
                    (off, page_row), last_index)
                raw, _ = _from_model_caches(kinds, new_caches)
                nxt = _select_rows(
                    logits._value[:, -1], key, do_sample, temperature, top_k,
                    top_p, token_mask=token_mask)
        finally:
            restore()
        return nxt[:, None], raw, first._value, nxt.astype(jnp.int32)

    return jax.jit(llm_mixed, donate_argnums=(2,))


class LLMEngine:
    def __init__(self, model, max_batch_slots=4, max_seq_len=512,
                 cache_dtype=None, eos_token_id=None, pad_token_id=0,
                 decode_chunk=1, max_queue_len=None, clock=None,
                 kv_layout=None,
                 page_size=128, num_pages=None, prefill_chunk=None,
                 prefix_cache=None, metrics_port=None, slo_targets=None,
                 flight_recorder_dir=None, healthy_heartbeat_age=60.0,
                 alert_rules=None, tracer=None, spec_k=0, spec_draft=None,
                 cache_aware_admission=False, admission_age_cap=4,
                 adapters=None, constraint_vocab=None, host_cache_pages=0,
                 disk_cache_dir=None, disk_cache_pages=0,
                 demote_watermark=0.25, demote_batch=8, state_checkpoints=None):
        """decode_chunk > 1 runs k decode steps per compiled call (a
        lax.scan), amortizing the host round-trip k-fold — the multi-step
        scheduling lever for high-latency hosts.  Slots that finish
        mid-chunk have their surplus tokens discarded (their cache rows are
        rewritten at the next admission), and admission/eos decisions
        happen every k tokens instead of every token.

        The page pool holds ``num_pages`` pages of ``page_size`` tokens
        (page 0 is the trash page); ``num_pages`` defaults to what slots *
        max_seq_len reserved rows would take (slots * max_seq_len /
        page_size + trash): size it by HBM budget to oversubscribe.
        Prompts prefill in ``prefill_chunk``-token chunks (default 128), one
        a tick.  A slot whose decode outruns the pool is preempted and
        requeued (llm_page_preemptions_total); a request that could never
        fit fails with ServerOverloadedError.  ``kv_layout`` selects
        nothing: ``None`` and ``"paged"`` are this one engine, ``"dense"``
        raises ``ValueError`` (``generate()`` keeps the dense static cache).

        ``prefix_cache`` (default on) shares kv pages across
        requests with a common prompt prefix: admission matches the prompt
        against a radix index of page-block hashes, maps the hit pages
        into the slot's table (refcounted), charges admission only for the
        unique pages, and prefills from the first uncached token.  Writes
        into a shared tail page fork it copy-on-write; unreferenced cached
        prefixes LRU-evict when the pool runs dry.  Greedy outputs are
        bitwise identical to prefix_cache=False.

        Degradation knobs (fault-tolerance layer): ``max_queue_len`` bounds
        the admission queue — submit() beyond it raises
        ServerOverloadedError instead of growing without bound; per-request
        ``timeout`` (see submit) expires requests in the queue and
        mid-decode with DeadlineExceededError; ``clock`` injects a time
        source for deterministic tests (default time.monotonic).

        Telemetry plane (README §Observability, "Endpoints & flight
        recorder"): ``metrics_port`` (0 = ephemeral) starts an HTTP
        exporter serving `/metrics`, `/healthz` (pump liveness +
        pump-heartbeat age) and `/varz`; it stops with ``stop()``.
        ``slo_targets`` maps {"ttft","e2e","queue_wait","tick"} to target
        seconds for the sliding-window SLO trackers (percentiles are
        tracked either way; targets add burn-rate accounting).
        ``flight_recorder_dir`` (or ``PADDLE_TPU_FLIGHT_DIR``) names where
        the black-box event ring is dumped when the pump watchdog trips.
        ``healthy_heartbeat_age`` bounds how stale the pump's heartbeat may
        grow before `/healthz` reports a wedge; the check stays green until
        the FIRST tick completes, so a long initial compile (the spike
        warmup() exists for) cannot fail a liveness probe.
        ``alert_rules`` (with ``metrics_port``) overrides the default alert
        rule set served on `/alertz` — each GET evaluates the engine
        against the local registry, so an external scraper polling
        `/alertz` gets current burn-rate / queue-backlog / healthcheck
        alert state without this process running its own evaluation loop.

        Request tracing (README §Observability, "Request tracing"): every
        request gets a per-request span tree — queue wait, each admission
        episode (with prefix-cache hit and requeue-reason attributes),
        every prefill chunk, coalesced decode summaries — tail-sampled
        into ``tracer.store`` (default: the process-global
        ``observability.tracing.TRACER``) and served on the exporter's
        `/tracez`.  The TTFT / e2e / queue-wait histograms carry the
        trace id as an OpenMetrics exemplar, and every flight-recorder
        event of the request carries it as ``trace_id`` — the aggregate
        planes point back at the exact request.  ``tracer=`` injects a
        private ``tracing.Tracer`` (its own store/sampling) for tests or
        per-engine isolation.

        ``spec_k > 0`` turns on SPECULATIVE decoding: each tick a
        host-side drafter (``spec_draft``: "ngram" prompt-lookup by
        default, any object with ``.propose``, or a small draft model —
        models/spec_decode.py) proposes K tokens per active slot and ONE
        compiled verify pass (S = K+1 through the same paged cache
        path) scores them all; the longest valid prefix plus one
        correction token is emitted, so a tick advances each slot by 1 to
        K+1 tokens.  Greedy outputs stay bitwise identical to spec_k=0;
        sampled slots use rejection sampling (distribution-preserving).
        Rollback is free: the slot's logical position simply does not
        advance past the accept point, and pages holding only
        rejected rows are decref'd back to the pool each tick
        (llm_spec_rolled_back_pages_total).  A verify that outruns the
        page pool preempts recompute-style exactly like decode.
        Incompatible with ``decode_chunk > 1`` (speculation already
        amortizes the host round-trip; stacking the two schedulers is
        unsupported).

        ``cache_aware_admission=True`` (needs the prefix cache) lets
        admission pick among the first few queued requests the one with
        the LONGEST cached prompt prefix instead of strict FIFO —
        back-to-back warm requests admit while a cold miss would have
        head-of-line blocked them.  Fairness: every time the queue head
        is passed over its ``adm_skips`` ages by one; once it reaches
        ``admission_age_cap`` the head admits next regardless of cache
        affinity (llm_admission_reorders_total counts the bypasses).

        ``adapters=`` attaches a shared
        ``models.lora.AdapterRegistry``: requests submitted with
        ``adapter_id=`` decode through that adapter's paged LoRA weight
        blocks — per-slot page rows gather into ONE compiled program, so
        a batch can mix adapters freely and swapping adapters never
        recompiles.  Admission charges the adapter pool like the kv pool:
        a request whose adapter cannot be loaded (every page pinned)
        waits at the head of the queue for a release; the reference drops
        on finish/expiry/preemption (llm_adapter_* metric family).
        ``constraint_vocab=`` (list: token id -> string) lets wire-form
        constraints (regex str / JSON-schema dict, e.g. from the router)
        be compiled replica-side; pre-compiled ``TokenConstraint``
        objects work without it.

        ``host_cache_pages > 0`` (needs the prefix cache) turns on the
        HIERARCHICAL KV tiers (README §Serving, "Hierarchical KV"): a
        background worker stages cold cached prefix pages device->host
        into a ``kv_host_cache.HostKVPool`` whenever the free-page ratio
        drops under ``demote_watermark`` (up to ``demote_batch`` pages
        per pass, ONE batched gather program), so a later LRU eviction
        DEMOTES the prefix instead of destroying it.  ``disk_cache_dir``
        (+ ``disk_cache_pages``) adds a third tier: host-RAM overflow
        spills to checksummed files (atomic tmp+rename; a torn spill
        quarantines on load and reads as a miss).  Admission PROMOTES
        staged blocks back with one batched host->device upload and
        prefills from the first truly-uncached token — eviction becomes
        a copy at PCIe/DRAM rates, not a re-prefill, and greedy decode
        stays bitwise identical to tiers off.

        WHAT EACH LAYER KEEPS is the model's to say.  A model
        with ``cache_kinds()`` returns one ``models.kv_cache.CacheKind`` a
        layer, and the engine allocates by it: K/V page pools at the
        layer's own head count and ``head_dim``, one latent page pool
        (``"paged_latent"``), fixed-size state a SLOT (``"recurrent"``), or
        nothing (feed-forward and expert layers);
        ``stats()["cache_kinds"]`` gives layers and bytes by kind.  A
        model without the method (Llama, GPT) pages K/V in every layer.
        ``models.nemotron_h.NemotronHForCausalLM`` keeps RECURRENT STATE in
        its Mamba-2 layers (``stats()["recurrent_state"]``): it is a
        slot's, not a page's — zeroed inside ``llm_prefill_chunk`` where a
        request's first chunk runs (a preempted and requeued request
        recomputes from nothing), carried from chunk to chunk, untouched by
        a final chunk's padded tail, and not advanced by a decode tick for
        slots that are idle or between chunks (``models.minicpm_sala``'s
        lightning-attention layers keep theirs the same way).  A shared
        page says nothing of the state at its end, so for such a model
        ``prefix_cache`` defaults to off; ``prefix_cache=True`` shares
        prefixes through STATE CHECKPOINTS: a fixed pool of
        ``state_checkpoints`` entries on the device (default 8; one entry
        = every recurrent layer's state of one sequence).  A prefill whose
        prompt ends on a page boundary indexes its pages, stores one after
        its last chunk and hangs it on the last page's node (any other
        prompt leaves nothing in the index: no checkpoint could follow, so
        its pages could never be resumed at); a later prompt matches up to the
        deepest node that carries one (never inside a page), the entry is
        copied into its slot where a fresh request's state is zeroed, and
        prefill starts at the first token past it; an entry goes with its
        node, or for a newer prefix when the pool is full
        (``stats()["recurrent_state"]["checkpoints"]``).  Greedy tokens
        equal those of ``prefix_cache=False``.  The host and disk tiers
        stage pages, not states, and a rejected draft cannot be rolled
        back out of a state: ``host_cache_pages > 0`` and ``spec_k > 0``
        raise ``ValueError`` for such a model.  A ``"paged_kv"`` layer
        with ``compressed`` keeps a third pool of compressed keys beside K
        and V and reads only the key blocks it selects
        (ops/sparse_attention.py); what it read comes back with the tick's
        tokens as the experts' pairs do (``stats()["sparse_attention"]``,
        ``llm_sparse_blocks_selected_total``).  A ``"paged_latent"`` layer
        (multi-head latent attention: ``models.deepseek_v3``) keeps ONE pool
        ``[pages, page_size, width]`` — a token's compressed latent and the
        rotary key its heads share, no V — behind the same page table,
        allocator, refcounts, prefix cache and copy-on-write fork; it is
        handed a ``LatentPaged`` and what its queries attended comes back
        the same way (``stats()["latent_attention"]``,
        ``llm_latent_context_tokens_total``).  ``cache_dtype="int8"``,
        ``host_cache_pages > 0`` and ``spec_k > 0`` raise ``ValueError`` for
        it: a latent row has no K/V pair to quantise a head, the tiers and
        a verify step have not been taken through a latent pool.  Expert layers that
        hold a share of the experts report their (token, expert) pairs:
        both programs add them to one device-resident total that comes
        back with the decode tick's tokens (``llm_moe_*``,
        ``stats()["moe"]``)."""
        cfg = model.config
        self.model = model
        self.n_slots = int(max_batch_slots)
        if kv_layout == "dense":
            raise ValueError(
                "kv_layout='dense' was removed: LLMEngine has one engine, the "
                "paged one (pass kv_layout=None or 'paged'); the dense static "
                "cache lives on in models.generation.generate(), the "
                "reference the engine's tokens are held to")
        if kv_layout not in (None, "paged"):
            raise ValueError(
                f"kv_layout must be None or 'paged', got {kv_layout!r}")
        lacks = [a for a in ("_supports_paged_cache", "prefill_chunk_step",
                             "generate_step") if not getattr(model, a, False)]
        if lacks:
            raise ValueError(
                f"{type(model).__name__} cannot be served by LLMEngine: it "
                f"lacks {', '.join(lacks)} (the paged kv-cache surface: "
                "attention that reads and writes page pools through a page "
                "table, models/kv_cache.py)")
        # what each layer keeps: the model says (one CacheKind a layer), or
        # every layer pages k/v (Llama, GPT)
        kinds = model.cache_kinds() if hasattr(model, "cache_kinds") else None
        self._cache_kinds = kinds
        self._recurrent = kinds is not None and any(
            k.kind == "recurrent" for k in kinds)
        if self._recurrent:
            # a shared page says nothing of the recurrent state at its end:
            # a prefix is resumed from a state CHECKPOINT (below), which the
            # tiers do not stage; and a state cannot be rolled back past a
            # rejected draft (ROADMAP R5)
            why = (f"{type(model).__name__} keeps recurrent state in some "
                   "layers: ")
            if host_cache_pages:
                raise ValueError(
                    why + "host_cache_pages stages prefix-cache pages in host "
                    "RAM, but not the state checkpoint a prefix of this model "
                    "is resumed from")
            if spec_k:
                raise ValueError(
                    why + "spec_k > 0 would need a rejected draft rolled back "
                    "out of the state, and a state has no past to return to")
            if prefix_cache is None:
                prefix_cache = False  # on request: it costs a checkpoint pool
        if state_checkpoints is not None and not (self._recurrent and prefix_cache):
            raise ValueError(
                "state_checkpoints sizes the pool of recurrent-state "
                "checkpoints: it needs a model with recurrent state and "
                "prefix_cache=True")
        if any(k.kind == "paged_latent" for k in kinds or ()):
            # one latent pool a layer: no K/V pair to quantise head by head,
            # nothing the tiers' page blocks or a verify ladder were tested on
            why = (f"{type(model).__name__} keeps one latent page pool a "
                   "layer (multi-head latent attention): ")
            if cache_dtype == "int8":
                raise ValueError(
                    why + "cache_dtype='int8' quantises K and V pages a head, "
                    "and a latent row has neither")
            if host_cache_pages:
                raise ValueError(
                    why + "host_cache_pages (the KV tiers) has not been "
                    "taken through a latent pool")
            if spec_k:
                raise ValueError(
                    why + "spec_k > 0 needs a verify step over the latent "
                    "pages, which the model does not have")
        if cache_dtype == "int8" and any(k.compressed for k in kinds or ()):
            raise ValueError(
                f"{type(model).__name__} selects key blocks by compressed keys "
                "kept beside the pages: cache_dtype='int8' has no such pool")
        self.ps = int(page_size)
        if self.ps < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        # pad L to a whole number of pages AND of 128-lane kernel tiles
        unit = math.lcm(self.ps, 128)
        self.L = -(-int(max_seq_len) // unit) * unit
        self.cache_dtype = cache_dtype
        self.eos = -1 if eos_token_id is None else int(eos_token_id)
        self.pad = int(pad_token_id)
        self._params, self._buffers = model.functional_state()
        # GQA models declare num_key_value_heads; MHA families (GPT) do not
        H = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
        # a model may state its head size (it need not divide the hidden one)
        D = getattr(cfg, "head_dim", None) \
            or cfg.hidden_size // cfg.num_attention_heads
        nl = cfg.num_hidden_layers
        B = self.n_slots
        kv_dtype = jnp.bfloat16 if str(
            next(iter(jax.tree_util.tree_leaves(self._params))).dtype
        ) == "bfloat16" else jnp.float32
        ps = self.ps
        self.M = self.L // ps  # page-table width (max pages per slot)
        P = int(num_pages) if num_pages is not None \
            else self.n_slots * self.M + 1
        P = max(P, 2)  # trash page + at least one allocatable page
        self.num_pages = P

        def pools(H, D):
            if cache_dtype == "int8":
                return (jnp.zeros((P, H, ps, D), jnp.int8),
                        jnp.zeros((P, H, ps, D), jnp.int8),
                        jnp.full((P, H, ps), 1e-8, jnp.float32),
                        jnp.full((P, H, ps), 1e-8, jnp.float32))
            return (jnp.zeros((P, H, ps, D), kv_dtype),
                    jnp.zeros((P, H, ps, D), kv_dtype))

        if kinds is None:
            self.caches = [pools(H, D) for _ in range(nl)]
        else:
            # one entry a layer, by its kind: page pools at the layer's
            # own head count and size, zeroed state a SLOT, or nothing
            def compressed(k):
                if not k.compressed:
                    return ()
                if ps % k.compressed:
                    raise ValueError(
                        f"page_size {ps} must hold whole strides of "
                        f"{k.compressed} tokens (one compressed key each)")
                return (jnp.zeros((P, k.kv_heads, ps // k.compressed,
                                   k.head_dim), kv_dtype),)

            from ..ops.latent_attention import pool_width

            self.caches = [
                pools(k.kv_heads, k.head_dim) + compressed(k)
                if k.kind == "paged_kv"
                else (jnp.zeros((P, ps, pool_width(k.latent_dim, k.rope_dim)),
                                kv_dtype),)
                if k.kind == "paged_latent"
                else tuple(jnp.zeros((B,) + tuple(shape), dt)
                           for _, shape, dt in k.state)
                for k in kinds]
        # host-side allocator: page 0 is the trash page, never handed
        # out; pop() order is deterministic (highest id first).  Pages
        # are REFCOUNTED: a page may be held by several slots (shared
        # prefix) and/or by one prefix-cache node; it returns to the
        # free list only when the last holder decrefs.
        self._free_pages = list(range(1, P))
        self._page_ref = np.zeros(P, np.int32)
        self._page_cached = np.zeros(P, bool)  # held by a cache node
        self._slot_pages: list[list[int]] = [[] for _ in range(B)]
        self._pt_host = np.zeros((B, self.M), np.int32)
        self.prefill_chunk = max(1, min(
            int(prefill_chunk) if prefill_chunk is not None else 128,
            self.L))
        if prefix_cache is None:
            prefix_cache = True  # the fleet default: share prefixes
        self._prefix = None
        if prefix_cache:
            from .prefix_cache import PrefixCache

            # a model with recurrent state resumes a prefix only where a
            # state checkpoint hangs, and indexes only prompts one can follow
            self._prefix = PrefixCache(self.ps, stateful=self._recurrent)
        # ---- state checkpoints: a fixed pool on the device, one entry =
        # every recurrent layer's state of one sequence; entries hang on
        # prefix-cache nodes (inference/prefix_cache.py)
        self._ckpt = None
        if self._prefix is not None and self._recurrent:
            n_ck = 8 if state_checkpoints is None else int(state_checkpoints)
            if n_ck < 1:
                raise ValueError(
                    f"state_checkpoints must be >= 1, got {state_checkpoints}")
            self._ckpt = [
                tuple(jnp.zeros((n_ck,) + tuple(shape), dt)
                      for _, shape, dt in k.state)
                for k in kinds if k.kind == "recurrent"]
            self._ckpt_free = list(range(n_ck))
            self._ckpt_capacity = n_ck
            self._ckpt_bytes = sum(
                int(np.prod(x.shape)) * x.dtype.itemsize
                for c in self._ckpt for x in c)
            self._ckpt_events = dict.fromkeys(_CKPT_EVENTS, 0)
            self._ckpt_store_jit = self._ckpt_load_jit = None
        self._prefix_hit_tokens = 0
        self._prefix_prompt_tokens = 0
        self._carried_chunks = 0  # prefill chunks that resumed their state
        # engine-local mirrors of the process-global counters, so
        # stats() stays per-engine (two engines in one process must not
        # read each other's forks/evictions)
        self._cow_copies = 0
        self._prefix_evictions = 0
        self._prefix_epoch = 0  # bumped on insert/evict: invalidates
                                # requests' memoized match results
        self._cow_jit = None
        # ---- hierarchical kv tiers (host RAM + disk under the radix
        # index): demotion stages pages AHEAD of eviction, promotion
        # re-uploads them at admission — README §Serving
        self._host_kv = None
        if host_cache_pages:
            if self._prefix is None:
                raise ValueError(
                    "host_cache_pages requires the prefix cache (the "
                    "tiers are keyed by its chained block hashes)")
            from .kv_host_cache import HostKVPool

            self._host_kv = HostKVPool(host_pages=host_cache_pages,
                                       disk_dir=disk_cache_dir,
                                       disk_pages=disk_cache_pages)
        self.demote_watermark = float(demote_watermark)
        self.demote_batch = max(1, int(demote_batch))
        self._gather_jit = None
        self._upload_jit = None
        self._demote_thread = None
        self._demote_mutex = threading.Lock()
        self._tier_hit_tokens = {"hbm": 0, "host": 0, "disk": 0}
        self._kv_demotions = 0
        self._kv_promotions = 0
        # expert layers report their pairs by held expert: a running total
        # on the device, [program, expert layer, held experts + touched],
        # that both programs add to and the decode tick brings back with its
        # tokens (one transfer); the pump publishes what was added since
        self._moe_kinds = [k for k in (kinds or ()) if k.experts_held]
        self._moe_acc = None
        if self._moe_kinds:
            if len({k.experts_held for k in self._moe_kinds}) != 1:
                raise ValueError("expert layers must hold the same number of "
                                 "experts (one accumulator row a layer)")
            shape = (len(_MOE_PROGRAMS), len(self._moe_kinds),
                     self._moe_kinds[0].experts_held + 1)
            self._moe_acc = jnp.zeros(shape, jnp.int32)
            self._moe_seen = np.zeros(shape, np.uint32)
            # rows x top_k and layer calls dispatched since the last fetch
            self._moe_pending = np.zeros((len(_MOE_PROGRAMS), 2), np.int64)
            self._moe_stats = {
                p: {"pairs_held": 0, "pairs_absent": 0, "experts_touched": 0,
                    "layer_calls": 0} for p in _MOE_PROGRAMS}
            self._moe_max_load = 0
        # block-sparse attention layers report (query calls, context blocks,
        # selected blocks) the same way: [program, 3] on the device
        self._sparse_acc = None
        if any(k.compressed for k in kinds or ()):
            self._sparse_acc = jnp.zeros(
                (len(_MOE_PROGRAMS), len(_SPARSE_FIELDS)), jnp.int32)
            self._sparse_seen = np.zeros(self._sparse_acc.shape, np.uint32)
            self._sparse_stats = {p: dict.fromkeys(_SPARSE_FIELDS, 0)
                                  for p in _MOE_PROGRAMS}
        # latent attention layers report (query calls, context tokens)
        self._latent_acc = None
        if any(k.kind == "paged_latent" for k in kinds or ()):
            self._latent_acc = jnp.zeros(
                (len(_MOE_PROGRAMS), len(_LATENT_FIELDS)), jnp.int32)
            self._latent_seen = np.zeros(self._latent_acc.shape, np.uint32)
            self._latent_stats = {p: dict.fromkeys(_LATENT_FIELDS, 0)
                                  for p in _MOE_PROGRAMS}
        # what the layers keep, for stats(): {kind: {"layers", "bytes"}}
        self._cache_stats = {}
        for i, c in enumerate(self.caches):
            d = self._cache_stats.setdefault(
                "paged_kv" if kinds is None else kinds[i].kind,
                {"layers": 0, "bytes": 0})
            d["layers"] += 1
            d["bytes"] += sum(int(np.prod(x.shape)) * x.dtype.itemsize
                              for x in c)
        self._prefilling = None  # (request, slot, prompt tokens consumed)
        self.slot_pos = np.zeros(B, np.int32)       # valid tokens per slot
        self.slot_req: list[_Request | None] = [None] * B
        self.last_token = np.full(B, self.pad, np.int32)
        # ---- the pump keeps ONE decode program in flight (_decode_tick):
        # what it dispatched and has not read, the tokens a slot has in it
        # (slot_pos and last_token are what the host has BOOKED), and the
        # last tokens of the newest program, which stay on the device and
        # feed the next one
        self._inflight: _InFlight | None = None
        self._ahead = np.zeros(B, np.int32)
        self._feed0 = self._feed = jnp.zeros((B,), jnp.int32)
        self._chunk_out = None  # a final chunk's (request, slot, logits)
        # until the tick reads them (_first_token)
        self._staged: _Chunk | None = None  # a mixed engine's chunk, from
        # _prefill_tick to the program that carries it (_decode_tick)
        self._pipeline = {"overlapped": 0, "mixed": 0, "surplus_tokens": 0,
                          "drained": dict.fromkeys(_DRAIN_REASONS, 0)}
        self.max_queue_len = None if max_queue_len is None \
            else int(max_queue_len)
        self._clock = clock if clock is not None else time.monotonic
        self._pump_error: BaseException | None = None
        self._stop_epoch = 0  # bumped by stop(): detects submit/stop races
        # Queue(maxsize=0) means UNBOUNDED, so max_queue_len=0 ("reject
        # everything": drain/maintenance mode) is enforced in submit()
        self._pending: "queue.Queue[_Request]" = queue.Queue(
            maxsize=self.max_queue_len
            if self.max_queue_len and self.max_queue_len > 0 else 0)
        self._rng = np.random.default_rng(1234)  # admission-token sampling
        self.decode_chunk = max(1, int(decode_chunk))
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if self.spec_k and self.decode_chunk > 1:
            raise ValueError(
                "spec_k and decode_chunk > 1 are mutually exclusive: "
                "speculative verify already amortizes the host round-trip "
                "(up to K+1 tokens per compiled call)")
        self._drafter = None
        if self.spec_k:
            from ..models.spec_decode import get_drafter

            self._drafter = get_drafter(spec_draft)
        # engine-local speculative counters (stats() stays per-engine; the
        # process-global registry series aggregate across engines)
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_rolled_back = 0
        self._spec_rb_pages = 0
        self._spec_verifies = 0
        self._recompute_tokens = 0  # prompt+prefix tokens re-prefilled
        # goodput ledger (ISSUE 20): serve-domain wall-clock + token
        # attribution; sections open only under the engine lock, so the
        # conservation invariant (sum(buckets) == wall span) holds after
        # every tick — tests assert it via self._goodput.check()
        self._goodput = _goodput.TimeLedger("serve")
        # the tick's exclusive phases (one clock read a boundary, the same
        # boundaries the goodput carves use) and what held the queue head
        self._phases = _PhaseClock("llm_tick", _TICK_PHASES)
        self._phase_pub = dict.fromkeys(_TICK_PHASES, 0.0)
        self._adm_blocked = dict.fromkeys(_BLOCK_REASONS, 0)
        self._sampler_ticks = dict.fromkeys(_SAMPLER_PATHS, 0)
        self.cache_aware = bool(cache_aware_admission)
        self.admission_age_cap = max(1, int(admission_age_cap))
        if self.cache_aware and self._prefix is None:
            raise ValueError(
                "cache_aware_admission requires the prefix cache (the reorder "
                "key IS the cached-prefix length)")
        self._adm_reorders = 0
        # -------------------------------------------- multi-tenant serving
        self.adapters = adapters
        if adapters is not None:
            from ..models.lora import AdapterRegistry

            if not isinstance(adapters, AdapterRegistry):
                raise TypeError(
                    "adapters= must be a models.lora.AdapterRegistry, got "
                    f"{type(adapters).__name__}")
        # _lora_args' tail for an engine with no adapter pool, built ONCE:
        # a jnp.zeros per compiled call would be an eager device program
        self._no_lora = ((), jnp.zeros((0,), jnp.int32))
        self._vocab = int(cfg.vocab_size)
        self._constraint_vocab = (list(constraint_vocab)
                                  if constraint_vocab is not None else None)
        self._constraint_cache = {}  # wire spec -> compiled TokenConstraint
        # reused on ticks with no constrained rows: an all-True mask is an
        # exact no-op through the fused sampler, so unconstrained batches
        # stay bitwise identical to a mask-free program — and the mask arg
        # is ALWAYS present, so turning constraints on never recompiles
        self._mask_all_true = jnp.ones((self.n_slots, self._vocab), bool)
        self._verify_jit = None
        self._decode_jit = {}  # scan length (effective chunk) -> jitted fn
        self._chunk_jit = None  # the one program that carries a chunk
        # ONE program for a tick's chunk and its decode rows, where the
        # model has the step and every row of a tick is one token of one
        # plain program: llm_mixed then takes llm_prefill_chunk's place.
        # (A LoRA epilogue gathers by batch row and a verify ladder or a
        # multi-token scan has no chunk-shaped twin: such engines keep the
        # two programs.)
        self._mixed = (callable(getattr(model, "mixed_step", None))
                       and self.decode_chunk == 1 and not self.spec_k
                       and adapters is None)
        # program_census(): built on the first call, never by a tick
        self._census = None
        self._census_lock = threading.Lock()
        self._device_time = None  # the last profile_device() result
        # page id -> trace_id of the request whose prefill first indexed
        # it in the prefix cache (the COW-fork provenance stamp; bounded
        # by num_pages since inserts overwrite reused page ids)
        self._page_donor = {}
        self._thread = None
        self._stop = False
        self._draining = False  # drain(): admission closed, in-flight finish
        self._adm_inflight = 0  # requests popped from the queue but not yet
        # in a slot/_prefilling/terminal — keeps _drained() from declaring
        # the engine empty mid-admission (pump thread owns the writes)
        self._lock = threading.Lock()
        # -------------------------------------------------- telemetry plane
        self._flight_dir = flight_recorder_dir \
            if flight_recorder_dir is not None \
            else os.environ.get("PADDLE_TPU_FLIGHT_DIR") or None
        self.slo_targets = dict(slo_targets or {})
        unknown = set(self.slo_targets) - set(_SLO_SERIES)
        if unknown:
            raise ValueError(
                f"slo_targets keys must be in {sorted(_SLO_SERIES)}, "
                f"got unknown {sorted(unknown)}")
        for key, series in _SLO_SERIES.items():
            if key in self.slo_targets:
                _slo.set_target(series, self.slo_targets[key])
        self._pump_heartbeat = None  # monotonic stamp of the last pump turn
        self._first_tick_done = False
        self.healthy_heartbeat_age = float(healthy_heartbeat_age)
        self._tracer = tracer if tracer is not None else _tracing.TRACER
        # always-on compile telemetry: backend compiles land on
        # jit_compiles_total{fn="backend"} even without a metrics port
        _profiling.install_compile_hooks()
        self.telemetry = None
        self.alert_engine = None
        if metrics_port is not None:
            from ..observability.alerts import AlertEngine
            from ..observability.exporter import TelemetryServer

            self.alert_engine = AlertEngine(rules=alert_rules)
            self.telemetry = TelemetryServer(
                port=metrics_port, recorder=_flight.RECORDER,
                alerts=self.alert_engine, traces=self._tracer)
            self.telemetry.register_healthcheck("pump", self._check_pump)
            self.telemetry.register_healthcheck(
                "pump_heartbeat", self._check_heartbeat)
            self.telemetry.register_healthcheck(
                "admission", self._check_admission)
            # refresh hbm_* gauges at scrape time + a /varz section
            self.telemetry.register_collect(
                _profiling.poll_device_memory, varz_key="device_memory")
            # goodput counters/ratio refresh at scrape time too (publish
            # pushes the delta since the last scrape), and the ledger
            # snapshot becomes a /varz section
            self.telemetry.register_collect(
                self._goodput.publish, varz_key="goodput")
            if self._host_kv is not None:
                # per-tier occupancy/hit-ratio on /varz — fleetwatch and
                # the router read this absent-not-zero (older replicas
                # simply have no prefix_tiers section)
                self.telemetry.register_collect(
                    self._tier_snapshot, varz_key="prefix_tiers")
            self.telemetry.start()
        elif alert_rules is not None:
            raise ValueError("alert_rules requires metrics_port (the rules "
                             "are served on the exporter's /alertz)")

    # --------------------------------------------------------- healthchecks

    def _check_pump(self):
        """Healthcheck: the background pump (when started) is alive and has
        not tripped the watchdog.  A never-started engine (caller-pumped
        synchronous mode) is healthy by definition."""
        if self._pump_error is not None:
            return False, f"pump died: {self._pump_error!r}"
        if self._thread is not None and not self._thread.is_alive() \
                and not self._stop:
            return False, "pump thread dead without a report"
        return True, "alive" if (self._thread is not None
                                 and self._thread.is_alive()) else "not started"

    def _check_heartbeat(self):
        """Healthcheck: the pump's last turn is recent — catches a pump
        WEDGED inside step() (alive but not progressing), which the
        liveness check above cannot see."""
        if self._thread is None or not self._thread.is_alive():
            return True, "pump not running"
        if self._pump_heartbeat is None:
            return True, "pump starting"
        if not self._first_tick_done:
            # the first tick pays every jit compile; a liveness probe must
            # not kill a pod that is merely compiling (use warmup() to
            # shrink this window)
            return True, "pump warming up (first tick may be compiling)"
        age = time.monotonic() - self._pump_heartbeat
        if age > self.healthy_heartbeat_age:
            return False, f"last pump turn {age:.1f}s ago"
        return True, f"last pump turn {age:.3f}s ago"

    def _check_admission(self):
        """Healthcheck: admission is open.  drain() flips this to failing
        with detail ``"draining"`` — `/healthz` goes 503 and the router
        (which probes per-replica health) stops routing here while the
        in-flight requests finish."""
        if self._draining:
            return False, "draining"
        return True, "accepting"

    # ------------------------------------------------------------- public

    def _compile_constraint(self, constraint):
        """Normalize submit()'s ``constraint=`` into a compiled, shared
        ``inference.constrain.TokenConstraint``.  Wire forms (regex str /
        JSON-schema dict, e.g. arriving via the router) compile once per
        distinct spec and memoize — same spec => the same automaton
        tables, so repeat traffic pays zero rebuild."""
        if constraint is None:
            return None
        if self.spec_k:
            raise ValueError(
                "constraint= does not compose with spec_k (constraint "
                "masks are per-position; drafted tokens cannot be "
                "pre-masked)")
        c = constraint
        if isinstance(c, (str, dict)):
            if self._constraint_vocab is None:
                raise ValueError(
                    "wire-form constraints (regex str / schema dict) need "
                    "the engine constructed with constraint_vocab= (token "
                    "id -> string); alternatively pass a compiled "
                    "TokenConstraint")
            if self.eos < 0:
                raise ValueError(
                    "constrained decoding needs eos_token_id configured on "
                    "the engine (the automaton terminates by emitting eos)")
            import json

            # no sort_keys: JSON-schema object property ORDER is part of
            # the compiled regex (declaration-order emission)
            key = c if isinstance(c, str) else json.dumps(c)
            cached = self._constraint_cache.get(key)
            if cached is None:
                from .constrain import compile_constraint

                cached = compile_constraint(c, self._constraint_vocab,
                                            self.eos)
                self._constraint_cache[key] = cached
            c = cached
        if not hasattr(c, "cursor"):
            raise TypeError(
                "constraint= must be a regex str, a JSON-schema dict, or a "
                f"compiled TokenConstraint, got {type(c).__name__}")
        if int(c.V) != self._vocab:
            raise ValueError(
                f"constraint vocab size {c.V} != model vocab size "
                f"{self._vocab}")
        if int(c.eos_token_id) != self.eos:
            raise ValueError(
                f"constraint eos {int(c.eos_token_id)} != engine eos "
                f"{self.eos}")
        return c

    def submit(self, prompt_ids, max_new_tokens=32, do_sample=False,
               temperature=1.0, top_k=0, top_p=1.0, timeout=None,
               trace_id=None, on_admit=None, adapter_id=None,
               constraint=None, on_token=None):
        """Queue one prompt; returns a Future of the generated id list.
        Sampling knobs are PER REQUEST — including ``top_k``: slots with
        different settings decode in the same compiled step (the fused
        sampler reads the k-th largest logit per row out of the sort the
        top-p mask needs anyway, so k never changes the program shape).

        ``timeout`` (seconds) sets a per-request deadline: a request still
        queued — or still decoding — when it expires fails with
        DeadlineExceededError and frees its slot.  When the admission queue
        is at max_queue_len the submit raises ServerOverloadedError (shed
        load with a reason, never grow without bound); a dead background
        pump raises immediately instead of handing back a future that can
        never complete.  A DRAINING engine (see drain()) likewise sheds
        with ServerOverloadedError while its in-flight requests finish.

        ``trace_id`` adopts an inherited trace id (a router propagating
        one request id across the wire) instead of minting a fresh one;
        ``on_admit`` is a zero-arg callback fired ONCE when the request
        first lands in a batch slot — the admission ack after which the
        request must not be retried elsewhere (it will produce output
        here).  ``on_token(index, token, t)`` fires from the pump once per
        generated token, in order, with the ``time.perf_counter()`` stamp
        the engine keeps for it (tokens of one tick share one stamp): the
        per-token clock for streaming and inter-token gaps.  It runs under
        the engine lock, so it must not block; a raising callback is
        swallowed like ``on_admit``'s.

        ``adapter_id`` decodes the request through a LoRA adapter
        registered on the engine's ``adapters=`` registry (per-request —
        one batch mixes adapters freely); ``constraint`` masks decoding
        to a token automaton: a regex str, a JSON-schema dict (compiled
        replica-side, needs ``constraint_vocab=``) or a pre-compiled
        ``TokenConstraint``.  Both validate here — bad adapter ids and
        malformed constraints fail at submit, never in the pump."""
        if self._pump_error is not None:
            raise RuntimeError(
                "LLMEngine pump thread died; restart the engine"
            ) from self._pump_error
        if self._thread is not None and not self._thread.is_alive() \
                and not self._stop:
            raise RuntimeError("LLMEngine pump thread died without a report; "
                               "restart the engine")
        if self._stop:
            # stop() is in progress: its drain may miss this request — fail
            # fast rather than hand back a future that cannot complete
            # (once stop() finishes, submit works again: caller-pumped or
            # after a fresh start())
            raise RuntimeError("LLMEngine is stopping; resubmit once stop() "
                               "completes")
        epoch = self._stop_epoch
        arr = np.asarray(
            prompt_ids._value if isinstance(prompt_ids, Tensor) else prompt_ids,
            np.int32).reshape(-1)
        if arr.size == 0 or arr.size > self.L - 1:
            raise ValueError(f"prompt length {arr.size} not in [1, {self.L - 1}]")
        if adapter_id is not None:
            if self.adapters is None:
                raise ValueError(
                    "adapter_id= requires an engine constructed with "
                    "adapters= (a models.lora.AdapterRegistry)")
            if adapter_id not in self.adapters.ids():
                raise KeyError(
                    f"unknown adapter {adapter_id!r}; register it on the "
                    "engine's AdapterRegistry first")
        try:
            cst = self._compile_constraint(constraint)
        except (TypeError, ValueError):
            _constrain.count_reject()  # validation rejects are violations
            raise
        now = self._clock()
        req = _Request(arr, int(max_new_tokens), Future(),
                       do_sample=bool(do_sample),
                       temperature=float(temperature), top_k=int(top_k),
                       top_p=float(top_p),
                       deadline=(now + float(timeout))
                       if timeout is not None else None,
                       submit_ts=now,
                       trace=self._tracer.start_trace(
                           "llm_request", trace_id=trace_id,
                           prompt_tokens=int(arr.size),
                           max_new_tokens=int(max_new_tokens)),
                       on_admit=on_admit, on_token=on_token,
                       adapter_id=adapter_id, constraint=cst,
                       cursor=cst.cursor() if cst is not None else None)
        if self._draining:
            _M_SHED.inc()
            self._goodput.count_tokens("shed", int(arr.size))
            _flight.record_event("shed", reason="draining",
                                 prompt_len=int(arr.size), **_trace_kv(req))
            req.trace.end(status="shed", reason="draining")
            raise ServerOverloadedError(
                "engine is draining (drain() in progress): new submits are "
                "rejected — route to another replica")
        try:
            if self.max_queue_len is not None and self.max_queue_len <= 0:
                raise queue.Full
            self._pending.put_nowait(req)
        except queue.Full:
            _M_SHED.inc()
            self._goodput.count_tokens("shed", int(arr.size))
            _flight.record_event("shed", queue_len=self.max_queue_len,
                                 prompt_len=int(arr.size), **_trace_kv(req))
            req.trace.end(status="shed", reason="queue_full")
            raise ServerOverloadedError(
                f"admission queue full ({self.max_queue_len} pending "
                f"requests); request rejected — retry with backoff") from None
        _M_SUBMITTED.inc()
        _M_QUEUE_DEPTH.set(self._pending.qsize())
        if self._pump_error is not None:
            # pump died between the entry check and the enqueue: the
            # watchdog's drain may have missed this request, so fail it
            # here rather than strand the future
            exc = RuntimeError("LLMEngine pump thread died; restart the "
                               "engine")
            _fail_future(req.future, exc)
            req.trace.end(status="error", error="pump died during submit")
            raise exc from self._pump_error
        if self._stop or self._stop_epoch != epoch:
            # stop() ran (or is running) concurrently with this submit: its
            # drain may have already swept the queue, stranding this
            # request with a server-mode caller blocked on the future
            exc = RuntimeError("LLMEngine stopped while the request was "
                               "being submitted; resubmit")
            _fail_future(req.future, exc)
            req.trace.end(status="error", error="stopped during submit")
            raise exc
        return req.future

    def generate(self, prompt_ids, max_new_tokens=32, **sampling):
        """Blocking single-prompt convenience."""
        fut = self.submit(prompt_ids, max_new_tokens, **sampling)
        self.run_until_complete()
        return fut.result()

    def run_until_complete(self):
        """Pump decode ticks until the queue and all slots drain and no
        decode result is left in flight."""
        while self._busy():
            self.step()

    def _busy(self):
        """Something is queued, mid-prefill, decoding, or dispatched and not
        read yet (a program whose every row has ended still has to be read:
        its counts, and the tokens it ran past an EOS)."""
        return (not self._pending.empty() or self._prefilling is not None
                or self._inflight is not None
                or any(r is not None for r in self.slot_req))

    @staticmethod
    def _hist_summary(hist):
        return {"count": hist.count, "sum": hist.sum,
                "mean": (hist.sum / hist.count) if hist.count else 0.0}

    def stats(self):
        """Operator snapshot — deliberately does NOT take the engine (pump)
        lock: a monitoring scrape must never block behind a wedged step(),
        and every field here is a single atomic read (the queue keeps its
        own mutex; the slot table is only ever swept, not summed, under
        the lock).  Values can therefore lag one tick — fine for stats.
        Request/latency series come from the process-global metrics
        registry, so two engines in one process share those counters.
        """
        pages_total = self.num_pages - 1
        # "in use" counts pages mapped by SLOTS; pages held only by the
        # prefix cache are reclaimable on demand and reported separately
        pages_used = self._slot_held_pages()
        prefix = None
        if self._prefix is not None:
            prompt_toks = self._prefix_prompt_tokens
            prefix = {
                "hit_ratio": self._prefix_hit_tokens / prompt_toks
                if prompt_toks else 0.0,
                "hit_tokens": self._prefix_hit_tokens,
                "prompt_tokens": prompt_toks,
                "cached_pages": int(self._page_cached.sum()),
                "shared_pages": int((self._page_ref > 1).sum()),
                "nodes": len(self._prefix),
                "cow_copies": self._cow_copies,
                "evictions": self._prefix_evictions,
            }
            tiers = self._tier_snapshot()
            if tiers is not None:
                # absent-not-zero: engines without the hierarchical tiers
                # simply have no "tiers" key (fleetwatch renders a dash)
                prefix["tiers"] = tiers
        spec = None
        if self.spec_k:
            spec = {
                "k": self.spec_k,
                "drafter": getattr(self._drafter, "name",
                                   type(self._drafter).__name__),
                "drafted_tokens": self._spec_drafted,
                "accepted_tokens": self._spec_accepted,
                "rolled_back_tokens": self._spec_rolled_back,
                "rolled_back_pages": self._spec_rb_pages,
                "verify_calls": self._spec_verifies,
                "acceptance_ratio": self._spec_accepted / self._spec_drafted
                if self._spec_drafted else 0.0,
            }
        cache_kinds = {k: dict(v) for k, v in self._cache_stats.items()}
        rec = cache_kinds.get("recurrent")
        ckpt = None
        if self._ckpt is not None:
            ckpt = dict(self._ckpt_events, capacity=self._ckpt_capacity,
                        held=self._ckpt_capacity - len(self._ckpt_free),
                        bytes=self._ckpt_bytes)
        sparse = None
        if self._sparse_acc is not None:
            sparse = {p: dict(v) for p, v in self._sparse_stats.items()}
            sparse["layers"] = sum(bool(k.compressed) for k in self._cache_kinds)
        latent = None
        if self._latent_acc is not None:
            latent = {p: dict(v) for p, v in self._latent_stats.items()}
            latent["layers"] = sum(k.kind == "paged_latent"
                                   for k in self._cache_kinds)
        moe = None
        if self._moe_kinds:
            moe = {p: dict(v) for p, v in self._moe_stats.items()}
            moe.update(expert_layers=len(self._moe_kinds),
                       experts_held=self._moe_kinds[0].experts_held,
                       max_expert_load=self._moe_max_load)
        return {
            "queue_depth": self._pending.qsize(),
            "active_slots": sum(r is not None for r in self.slot_req),
            "n_slots": self.n_slots,
            "kv_layout": "paged",  # fleetwatch and the replica wire show it
            # what the layers keep: {kind: {"layers", "bytes"}} over
            # paged_kv, paged_latent, recurrent and none
            "cache_kinds": cache_kinds,
            # per-slot state of the recurrent layers; None without any
            # (+ the pool of state checkpoints under the prefix cache)
            "recurrent_state": None if rec is None else {
                "bytes": rec["bytes"], "slots": self.n_slots,
                "layers": rec["layers"], "checkpoints": ckpt,
                "carried_chunks": self._carried_chunks},
            # block-sparse attention layers: query calls (a real row a
            # layer), their contexts' blocks and the blocks they selected,
            # by program; None without any
            "sparse_attention": sparse,
            # latent attention layers: query calls (a real row a layer) and
            # the tokens of their contexts, by program; None without any
            "latent_attention": latent,
            # expert layers' routed pairs by program; None without any
            "moe": moe,
            "llm_kv_pages_in_use": pages_used,
            "kv_pages_total": pages_total,
            "kv_page_utilization": pages_used / pages_total
            if pages_total else 0.0,
            "prefix_cache": prefix,
            "spec": spec,
            "adapters": self.adapters.stats()
            if self.adapters is not None else None,
            "admission_reorders": self._adm_reorders,
            # ticks that left the queue head waiting, by what held it
            "admission_blocked": dict(self._adm_blocked),
            # ticks by what their knobs made the sampler run: sampled =
            # some row drew, threshold = the vocabulary was sorted
            "sampler": {
                "ticks": sum(self._sampler_ticks.values()),
                "sampled_ticks": self._sampler_ticks["draw"]
                + self._sampler_ticks["threshold"],
                "threshold_ticks": self._sampler_ticks["threshold"],
            },
            # the pump's exclusive phases, cumulative since construction;
            # host_s leaves out the phases that only wait for the device
            "tick_phases": {
                "seconds": dict(self._phases.seconds),
                "count": dict(self._phases.count),
                "host_s": sum(v for p, v in self._phases.seconds.items()
                              if p not in _SYNC_PHASES),
            },
            # the decode pipeline: results read with the next program
            # already dispatched, results read without (by why), and tokens
            # a program computed for a row that had ended before they were
            # read (an EOS one program late, an expiry)
            "tick_pipeline": dict(
                self._pipeline, drained=dict(self._pipeline["drained"])),
            "prefill_in_progress": self._prefilling is not None,
            "pump_alive": self._thread.is_alive()
            if self._thread is not None else False,
            "pump_error": repr(self._pump_error)
            if self._pump_error is not None else None,
            "stopping": self._stop,
            "draining": self._draining,
            "requests": {
                "submitted": _M_SUBMITTED.value,
                "admitted": _M_ADMITTED.value,
                "completed": _M_COMPLETED.value,
                "shed": _M_SHED.value,
                "expired_queued": _M_EXPIRED.labels(where="queued").value,
                "expired_inflight": _M_EXPIRED.labels(where="inflight").value,
            },
            "decode_tokens": _M_DECODE_TOKENS.value,
            "decode_tokens_per_second": _M_DECODE_TPS.value,
            # attention dispatch decisions (trace-time, process-global):
            # {(path, reason): count} from llm_attn_kernel_total — a
            # "paged_dense" entry on a TPU engine means some compiled
            # program fell off the ragged-kernel path
            "attn_dispatch": {
                "/".join(labels): count
                for labels, count in _attn_dispatch_series()},
            "queue_wait_seconds": self._hist_summary(_M_QUEUE_WAIT),
            "ttft_seconds": self._hist_summary(_M_TTFT),
            "e2e_seconds": self._hist_summary(_M_E2E),
            # sliding-window percentiles + burn rates (observability.slo);
            # like the registry series these are process-global
            "slo": _slo.summary(prefix="llm_"),
            # goodput ledger (ISSUE 20): wall-clock buckets + token classes
            # for THIS engine — snapshot only, no conservation check here
            # (stats() must never raise on a mid-tick scrape)
            "goodput": self._goodput.snapshot(),
            "recompute_tokens": self._recompute_tokens,
            # tracer sampling health (started/sampled/dropped + store
            # occupancy) — fleetwatch's view of whether /tracez is useful
            "tracing": self._tracer.stats(),
            # per-device HBM occupancy (empty on backends that expose no
            # memory_stats — CPU); polling here also refreshes the
            # hbm_* gauges
            "device_memory": _profiling.poll_device_memory(),
            # the last profile_device(): device seconds by program and
            # first scope component (None until one has run)
            "device_time": self._device_time,
            "telemetry_url": self.telemetry.url
            if self.telemetry is not None else None,
        }

    def start(self):
        """Background pump (server mode).  Re-starts the telemetry exporter
        when the engine was configured with one and a prior stop() shut it
        down (port 0 rebinds a fresh ephemeral port)."""
        if self.telemetry is not None and not self.telemetry.running():
            self.telemetry.start()
        if self._thread is None or not self._thread.is_alive():
            self._stop = False
            self._pump_error = None
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()
        if self._host_kv is not None \
                and (self._demote_thread is None
                     or not self._demote_thread.is_alive()):
            # demotion worker: device->host staging stays OFF the decode
            # tick (synchronous engines call demote_step() themselves)
            self._demote_thread = threading.Thread(
                target=self._demote_loop, daemon=True)
            self._demote_thread.start()
        return self

    def stop(self):
        """Halt the pump and FAIL any queued/in-flight requests — a client
        blocked on future.result() must not hang forever.  Afterwards the
        engine is clean and reusable: synchronous (caller-pumped) use and
        start() both work again.  Stops the telemetry exporter too — the
        clean-shutdown contract that keeps tier-1 from leaking sockets."""
        if self.telemetry is not None:
            self.telemetry.stop()
        self._stop = True
        self._stop_epoch += 1
        wedged = False
        if self._thread is not None:
            self._thread.join(timeout=30)
            wedged = self._thread.is_alive()
            if not wedged:
                self._thread = None
        if wedged:
            # the pump is stuck inside step() HOLDING the engine lock:
            # taking it here would hang stop() past its own join timeout.
            # Fail queued requests now (the queue has its own mutex); the
            # pump's _loop drains in-flight slots itself when the wedged
            # step finally returns and it observes _stop.  _stop stays
            # raised and _thread stays set so start() cannot double-pump.
            self._drain_queue(RuntimeError("LLMEngine stopped"))
        else:
            self._fail_pending(RuntimeError("LLMEngine stopped"))
            if self._host_kv is not None \
                    and self._demote_thread is not None:
                # pump terminated => the engine lock is free, so the
                # worker exits at its next _stop check — join BEFORE the
                # _stop reset below would resurrect its loop
                self._demote_thread.join(timeout=5)
                if not self._demote_thread.is_alive():
                    self._demote_thread = None
            # a fully-terminated pump leaves the engine clean and reusable
            self._stop = False

    # ------------------------------------------------------------ draining

    def _drained(self):
        """True when nothing is queued, in the pump's hands mid-admission,
        mid-prefill, decoding, or dispatched and unread."""
        return self._adm_inflight == 0 and not self._busy()

    def drain(self, timeout=None, deadline_s=None):
        """Graceful drain — the zero-loss half of a rolling restart.

        Flips the engine to DRAINING: new submits shed with
        ServerOverloadedError, the "admission" healthcheck fails (so
        `/healthz` goes 503 with detail ``"draining"`` and a router stops
        sending traffic here), but everything already queued or in flight
        RUNS TO COMPLETION — the contract stop() deliberately does not
        offer (stop fails in-flight requests).  Idempotent; stays in
        draining mode until resume() (so a controller can drain, restart,
        then resume).

        Joinable: blocks until the engine is empty and returns True, or
        returns False when ``timeout`` (seconds, monotonic) elapses first
        or the pump dies/stops mid-drain — ``timeout`` gives up WITHOUT
        touching the remaining work (it keeps running).

        ``deadline_s`` is the HARD bound a supervisor-driven SIGTERM
        drain needs: when it expires, every request still queued or in
        flight is failed with ``DeadlineExceededError`` (never silently
        dropped — each is counted on ``llm_drain_expired_total`` and its
        future resolves with the error) and drain returns True with the
        engine EMPTY, so shutdown can always proceed."""
        self._draining = True
        _M_DRAINING.set(1.0)
        _flight.record_event("drain_begin",
                             queue_depth=self._pending.qsize())
        deadline = None if timeout is None \
            else self._clock() + float(timeout)
        hard = None if deadline_s is None \
            else self._clock() + float(deadline_s)
        while not self._drained():
            if hard is not None and self._clock() >= hard:
                # deadline expired: fail the remainder LOUDLY and finish
                # the drain — a wedged request must not wedge shutdown.
                # _fail_pending serializes on the engine lock, so a live
                # pump mid-step finishes its step first.
                n = self._fail_pending(DeadlineExceededError(
                    f"drain deadline ({deadline_s}s) expired"))
                _M_DRAIN_EXPIRED.inc(n)
                _flight.record_event("drain_expired", failed=n)
                break
            if self._pump_error is not None or self._stop:
                return False
            if deadline is not None and self._clock() > deadline:
                return False
            if self._thread is not None and self._thread.is_alive():
                time.sleep(0.002)  # the background pump is doing the work
            elif self._thread is not None:
                return False  # pump died without a report mid-drain
            else:
                self.step()
        _flight.record_event("drain_complete")
        return True

    def resume(self):
        """Exit draining mode: admission reopens, `/healthz` recovers."""
        self._draining = False
        _M_DRAINING.set(0.0)
        _flight.record_event("drain_resume")
        return self

    def _loop(self):
        try:
            while not self._stop:
                self._pump_heartbeat = time.monotonic()
                if not self._busy():
                    time.sleep(0.002)
                    continue
                self.step()
            # normal _stop exit: drain (idempotent vs stop()'s own drain) —
            # this is what frees in-flight slots when stop() had to give up
            # on a wedged step and could not take the engine lock itself
            self._fail_pending(RuntimeError("LLMEngine stopped"))
        except BaseException as e:  # watchdog: a dying pump must not strand
            self._pump_error = e    # callers blocked on future.result()
            _M_WATCHDOG.inc()
            _flight.record_event("watchdog_trip", error=repr(e))
            try:
                # fail (and trace-end) the in-flight requests BEFORE the
                # dump: the black box's sibling traces_*.json then holds
                # the dying requests' span trees, not just their events
                self._fail_pending(RuntimeError(
                    f"LLMEngine pump thread died: {e!r}"))
            finally:
                # best-effort black box; safe_dump never masks the crash
                _flight.safe_dump(self._flight_dir, reason="watchdog_trip",
                                  extra={"error": repr(e)})

    def _drain_queue(self, exc):
        """Fail every QUEUED request (the queue has its own mutex — safe
        without the engine lock).  Returns how many were failed."""
        n = 0
        while not self._pending.empty():
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            _fail_future(req.future, exc)
            self._end_trace(req, "error", error=repr(exc))
            n += 1
        return n

    def _fail_pending(self, exc):
        """Fail every queued and in-flight request with `exc`.  Takes the
        engine lock: a caller thread pumping run_until_complete must not
        race the dying background pump on the slot table (step() released
        the lock when its exception unwound).  Returns how many requests
        were failed."""
        with self._lock:
            n = self._drain_queue(exc)
            if self._inflight is not None:
                # dropped unread (the device may be what failed); its
                # counts stay in the device's totals and come home with the
                # next program's
                self._inflight = None
                self._count_drain("stop")
            self._feed = self._feed0
            self._chunk_out = self._staged = None
            if self._prefilling is not None:
                req, slot, _ = self._prefilling
                self._prefilling = None
                self._release_pages(slot)
                self._release_adapter(req)
                _fail_future(req.future, exc)
                self._end_trace(req, "error", error=repr(exc))
                n += 1
            for i, req in enumerate(self.slot_req):
                if req is not None:
                    self._vacate(i)
                    _fail_future(req.future, exc)
                    self._end_trace(req, "error", error=repr(exc))
                    n += 1
            return n

    # --------------------------------------------------- request tracing

    def _flush_decode_span(self, req):
        """Close the request's current coalesced decode window into ONE
        summary span (ticks + tokens attributes) — called at the window
        bound, at finish, and before any requeue/expiry, so a trace holds
        a bounded number of decode spans no matter how long it decoded."""
        window = req.token_ts[req.dec_i0:]
        if window and req.trace:
            # the window's ticks are its distinct stamps: the tokens of
            # one tick (a decode chunk, a verify ladder) share one
            ticks = len(set(window))
            req.trace.add_span(
                "decode",
                duration_s=max(0.0, time.perf_counter() - window[0]),
                ticks=ticks, tokens=len(window))
            if req.spec_drafted:
                # speculative summary triplet for the same window: the
                # spec envelope plus its draft/verify phase breakdown,
                # each carrying the window's mean accepted_len
                acc_len = round(req.spec_accepted / ticks, 3)
                req.trace.add_span(
                    "spec",
                    duration_s=req.spec_draft_s + req.spec_verify_s,
                    drafted=int(req.spec_drafted),
                    accepted=int(req.spec_accepted),
                    accepted_len=acc_len)
                req.trace.add_span("draft", duration_s=req.spec_draft_s,
                                   tokens=int(req.spec_drafted))
                req.trace.add_span("verify", duration_s=req.spec_verify_s,
                                   accepted_len=acc_len)
        req.dec_i0 = len(req.token_ts)
        req.spec_drafted = 0
        req.spec_accepted = 0
        req.spec_draft_s = 0.0
        req.spec_verify_s = 0.0

    def _end_trace(self, req, status, **attrs):
        """Terminal trace bookkeeping for a request leaving the engine:
        flush the decode window, close a dangling admission span, end the
        trace and hand it to the tail sampler (idempotent)."""
        self._flush_decode_span(req)
        if req.adm_span is not None:
            req.adm_span.close(error=None if status == "ok" else status)
            req.adm_span = None
        ts = req.token_ts
        if ts and req.trace:
            attrs["ttft_s"] = req.trace.rel_s(ts[0])
            if len(ts) > 1:
                attrs["token_gap_max_s"] = max(
                    b - a for a, b in zip(ts, ts[1:]))
                attrs["token_gap_mean_s"] = (ts[-1] - ts[0]) / (len(ts) - 1)
        req.trace.end(status=status, generated_tokens=len(req.tokens),
                      **attrs)

    @staticmethod
    def _emit_token(req, tok, t):
        """One generated token leaves the pump: kept with its stamp ``t``
        (``time.perf_counter()``), streamed to ``on_token``."""
        req.tokens.append(tok)
        req.token_ts.append(t)
        if req.on_token is not None:
            try:
                req.on_token(len(req.tokens) - 1, tok, t)
            except Exception:
                pass  # a failing stream callback must never kill the pump

    def _first_token_out(self, req, first):
        """The admission's final step: the first token has
        just been emitted.  Closes the admission span; on the request's
        FIRST admission stamps ``ttft_s`` on the trace (the same instant,
        relative to the trace's start) and observes ``llm_ttft_seconds``."""
        req.dec_i0 = len(req.token_ts)  # decode windows hold decode tokens
        if req.adm_span is not None:
            req.adm_span.close()
            req.adm_span = None
        if first and req.submit_ts is not None:
            req.trace.set_attr("ttft_s", req.trace.rel_s(req.token_ts[0]))
            self._observe_ttft(req)

    def _trace_queue_wait(self, req):
        """First-admission queue-wait: histogram (+trace exemplar), SLO
        verdict onto the trace, queue_wait span."""
        wait = max(0.0, req.admit_ts - req.submit_ts)
        _M_QUEUE_WAIT.observe(wait, exemplar=req.trace.trace_id or None)
        if _slo.track("llm_queue_wait", wait):
            req.trace.mark_slo("llm_queue_wait")
        req.trace.add_span("queue_wait", duration_s=wait)
        return wait

    def _open_admission_span(self, req, slot, **attrs):
        """One "admission" span per EPISODE: a preempted/requeued request
        re-admits under a new span carrying the requeue reason — its
        trace shows every attempt, not just the last."""
        req.adm_episode += 1
        attrs = {"slot": int(slot), "episode": req.adm_episode,
                 "prompt_tokens": int(req.prompt.size), **attrs}
        if req.requeue_reason:
            attrs["requeue_reason"] = req.requeue_reason
            req.requeue_reason = None
        req.adm_span = req.trace.span("admission", **attrs).open()
        if req.on_admit is not None:
            # admission ack: fired exactly once (re-admissions after a
            # preemption requeue are the SAME request — still admitted)
            cb, req.on_admit = req.on_admit, None
            try:
                cb()
            except Exception:
                pass  # a failing ack callback must never kill the pump

    def _observe_ttft(self, req):
        """The admission token IS the first token out."""
        ttft = max(0.0, self._clock() - req.submit_ts)
        _M_TTFT.observe(ttft, exemplar=req.trace.trace_id or None)
        if _slo.track("llm_ttft", ttft):
            req.trace.mark_slo("llm_ttft")

    # --------------------------------------------------------- internals

    def _blocked(self, reason):
        """This tick leaves the queue head waiting: count what held it."""
        self._adm_blocked[reason] += 1
        _ADM_BLOCKED_SERIES[reason].inc()

    def _caches_alive(self):
        """False when the kv cache buffers were consumed by a donating
        compiled call that then failed mid-execution — the engine must not
        keep serving on deleted arrays (trace/compile-time failures raise
        BEFORE donation is consumed, so those stay per-request)."""
        try:
            return not any(
                getattr(x, "is_deleted", lambda: False)()
                for c in self.caches for x in c)
        except Exception:
            return False

    # ------------------------------------------------- page-pool internals

    def _incref(self, page):
        self._page_ref[page] += 1

    def _decref(self, page):
        """Drop one hold on a page; the LAST holder frees it.  A negative
        refcount means a double-free — fail loudly, a silently corrupted
        allocator serves one slot's kv to another."""
        r = int(self._page_ref[page]) - 1
        if r < 0:
            raise AssertionError(f"kv page {page} decref below zero")
        self._page_ref[page] = r
        if r == 0:
            self._free_pages.append(page)

    def _release_pages(self, slot):
        """Decref every page a slot holds (finish/expiry/preempt/stop) and
        point its page-table row back at the trash page.  Shared pages
        survive in other slots / the prefix cache; exclusive ones free."""
        if not self._slot_pages[slot]:
            return
        for page in self._slot_pages[slot]:
            self._decref(page)
        self._slot_pages[slot] = []
        self._pt_host[slot, :] = 0

    def _vacate(self, slot):
        """The slot's request leaves it (finish, expiry, preemption, stop):
        its pages and its adapter page go back; returns the request.  A
        decode program already dispatched may still write the row's next
        token into one of those pages.  That is sound by device order:
        whoever is handed the page next writes it in a LATER program, and no
        page that others read is ever written (_cow_page).  The token itself
        is dropped where the program is read (_book): the slot no longer
        holds the request."""
        req = self.slot_req[slot]
        self.slot_req[slot] = None
        self.last_token[slot] = self.pad
        self._ahead[slot] = 0
        self._release_pages(slot)
        self._release_adapter(req)
        return req

    def _alloc_pages(self, slot, n):
        """Move n pages from the free list into a slot's table (refcount 1:
        exclusively owned); returns False (allocating nothing) if the pool
        cannot cover the request even after evicting unreferenced cached
        prefixes."""
        if n <= 0:
            return True
        if len(self._free_pages) < n and \
                not self._evict_prefix(n - len(self._free_pages)):
            return False
        for _ in range(n):
            page = self._free_pages.pop()
            self._page_ref[page] = 1
            self._pt_host[slot, len(self._slot_pages[slot])] = page
            self._slot_pages[slot].append(page)
        return True

    def _evict_prefix(self, need):
        """LRU-evict cached prefixes nobody references until ``need`` more
        pages are free.  Only leaves whose page is held by the cache ALONE
        are candidates — a page mapped by a live slot frees nothing (and a
        matched chain must stay intact under its reader)."""
        if self._prefix is None:
            return False
        if self._prefix.freeable_count(
                lambda p: int(self._page_ref[p]) > 1) < need:
            # eviction could not cover the allocation anyway: keep the warm
            # entries instead of destroying cache for a doomed alloc
            return False
        freed = 0
        while freed < need:
            evicted = self._prefix.evict_one(
                lambda p: int(self._page_ref[p]) == 1
                and bool(self._page_cached[p]))
            if evicted is None:
                return False
            key, _tokens, page, _ntok = evicted
            # hierarchical tiers: a page the demotion worker already staged
            # host-side survives this eviction as a DEMOTION (the host/disk
            # entry under the same chain key re-promotes at admission); an
            # unstaged page is destroyed exactly as before
            if self._host_kv is not None and key in self._host_kv:
                _flight.record_event("kv_demote_complete", page=int(page))
            self._page_cached[page] = False
            self._decref(page)
            _M_PREFIX_EVICT.inc()
            self._prefix_evictions += 1
            self._prefix_epoch += 1
            freed += 1
            self._ckpt_reclaim()
        return True

    def _get_cow_copy(self):
        if self._cow_jit is None:
            from ..models.kv_cache import cow_copy_pages as copy_pools

            kinds = self._cache_kinds

            def cow_copy_pages(caches, src, dst):
                # only page pools fork: a state layer's arrays are a slot's
                if kinds is None:
                    return copy_pools(caches, src, dst)
                paged = [i for i, k in enumerate(kinds)
                         if k.kind in ("paged_kv", "paged_latent")]
                out = list(caches)
                for i, c in zip(paged, copy_pools([caches[i] for i in paged], src, dst)):
                    out[i] = c
                return out

            _profiling.record_compile("cow_copy")
            self._cow_jit = jax.jit(cow_copy_pages, donate_argnums=(0,))
        return self._cow_jit

    # ---------------------------------------------------- state checkpoints

    def _state_layers(self):
        return [i for i, k in enumerate(self._cache_kinds)
                if k.kind == "recurrent"]

    def _get_ckpt_store(self):
        """(caches, pool, slot, entry) -> pool with the slot's state of
        every recurrent layer written into the entry (the pool is donated)."""
        if self._ckpt_store_jit is None:
            layers = self._state_layers()

            def store(caches, pool, slot, entry):
                with jax.named_scope("state_checkpoint"):
                    return [tuple(p.at[entry].set(x[slot])
                                  for p, x in zip(pl, caches[i]))
                            for pl, i in zip(pool, layers)]

            _profiling.record_compile("state_checkpoint_store")
            self._ckpt_store_jit = jax.jit(store, donate_argnums=(1,))
        return self._ckpt_store_jit

    def _get_ckpt_load(self):
        """(caches, pool, slot, entry) -> caches with the entry copied into
        the slot's state of every recurrent layer (the caches are donated)."""
        if self._ckpt_load_jit is None:
            layers = self._state_layers()

            def load(caches, pool, slot, entry):
                out = list(caches)
                with jax.named_scope("state_checkpoint"):
                    for pl, i in zip(pool, layers):
                        out[i] = tuple(x.at[slot].set(p[entry])
                                       for p, x in zip(pl, caches[i]))
                return out

            _profiling.record_compile("state_checkpoint_load")
            self._ckpt_load_jit = jax.jit(load, donate_argnums=(0,))
        return self._ckpt_load_jit

    def _ckpt_event(self, event, n=1):
        self._ckpt_events[event] += n
        _M_STATE_CKPT.labels(event=event).inc(n)

    def _ckpt_reclaim(self):
        """Entries whose node the index dropped go back to the pool."""
        if self._ckpt is None or not self._prefix.released:
            return
        self._ckpt_event("evicted", len(self._prefix.released))
        self._ckpt_free.extend(self._prefix.released)
        self._prefix.released.clear()

    def _ckpt_store(self, slot, req):
        """After the last chunk of a prompt that ends on a page boundary:
        save the slot's state and hang it on that page's node, so a later
        prompt with this prefix resumes here.  A full pool gives up its
        least recently used entry."""
        key = self._prefix.checkpoint_node(req.prompt, adapter_id=req.adapter_id)
        if key is None:
            return
        if not self._ckpt_free:
            entry = self._prefix.steal_checkpoint()
            if entry is None:
                return
            self._ckpt_event("evicted")
        else:
            entry = self._ckpt_free.pop()
        self._ckpt = self._get_ckpt_store()(
            self.caches, self._ckpt, np.int32(slot), np.int32(entry))
        self._prefix.attach_checkpoint(key, entry)
        self._prefix_epoch += 1  # what match() returns has changed
        self._ckpt_event("stored")

    def _cow_page(self, slot, idx):
        """Copy-on-write guard for a slot about to WRITE rows of its
        page-table entry ``idx``: a shared page (other slots and/or the
        prefix cache read it) is forked — rows copied into a fresh page,
        the slot's table repointed, the original decref'd — so readers
        keep the frozen kv.  When the ONLY other holder is the prefix
        cache and no page can be freed, the slot steals the page back
        (evicts the cache node, writes in place) instead of failing.
        Returns False only when a genuinely-needed copy found no page."""
        pages = self._slot_pages[slot]
        if idx >= len(pages):
            return True  # not allocated yet: the grower hands out a fresh one
        old = pages[idx]
        if int(self._page_ref[old]) <= 1:
            return True  # exclusive: write in place
        if self._free_pages or self._evict_prefix(1):
            new = self._free_pages.pop()
            self._page_ref[new] = 1
            try:
                # numpy scalars: jnp.asarray(int, dtype) is an eager
                # convert_element_type program, two a fork (_sampling_knobs)
                self.caches = self._get_cow_copy()(
                    self.caches, np.int32(old), np.int32(new))
            except Exception:
                # the copy donates self.caches; the caller's _caches_alive
                # check escalates a consumed-buffer failure to the watchdog
                self._page_ref[new] = 0
                self._free_pages.append(new)
                raise
            pages[idx] = new
            self._pt_host[slot, idx] = new
            self._decref(old)
            _M_COW.inc()
            self._cow_copies += 1
            r = self._req_for_slot(slot)
            if r is not None:  # the fork is part of the request's story
                r.trace.inc_attr("cow_forks")
            return True
        if int(self._page_ref[old]) == 2 and self._page_cached[old] \
                and self._prefix is not None \
                and self._prefix.evict_page(old) is not None:
            # steal-back: the diverging tail is the least valuable entry in
            # the cache anyway — reclaim it rather than preempt the slot
            self._page_cached[old] = False
            self._decref(old)
            _M_PREFIX_EVICT.inc()
            self._prefix_evictions += 1
            self._prefix_epoch += 1
            self._ckpt_reclaim()
            return True
        return False

    # ------------------------------------------------- hierarchical kv tiers

    def _get_gather(self):
        if self._gather_jit is None:
            from ..models.kv_cache import gather_pages_to_host

            _profiling.record_compile("kv_gather")
            # NOT donated: the gather only READS the pools; later donating
            # programs (decode/prefill) serialize behind it in dispatch
            # order, so the snapshot is consistent with the allocator state
            # at dispatch time
            self._gather_jit = jax.jit(gather_pages_to_host)
        return self._gather_jit

    def _get_upload(self):
        if self._upload_jit is None:
            from ..models.kv_cache import upload_host_pages

            _profiling.record_compile("kv_upload")
            self._upload_jit = jax.jit(upload_host_pages,
                                       donate_argnums=(0,))
        return self._upload_jit

    def demote_step(self, force=False):
        """ONE demotion pass: stage up to ``demote_batch`` least-recently-
        used cached prefix pages device->host, so a later LRU eviction
        completes as a tier DEMOTION instead of destroying the prefix.

        Runs on the background demotion worker (start()), or synchronously
        from tests/operators — NEVER on the decode tick.  Gated by page
        AND device-memory pressure unless ``force``: demotion proceeds
        when ``max(1 - free_page_ratio, hbm_utilization_ratio)`` crosses
        ``1 - demote_watermark`` — a pool that still has free pages but
        whose device is near its HBM limit (other pools, activation
        spikes) starts staging early.  The HBM term reads the PR-14
        ``memory_stats()`` poll and is absent-tolerant: CPU backends
        report nothing, the term is 0, and the gate degrades to the
        original free-page watermark.  Lock protocol: the memory poll
        (a host call per device) runs BEFORE the engine lock; candidate
        scan + ONE batched gather dispatch under the engine lock
        (dispatch is async), the blocking device->host fetch OUTSIDE it,
        commit under the lock again — the decode tick never waits on a
        transfer.  Cached pages are frozen (COW forks or steals them
        before any write) and keys are content-addressed, so the fetched
        snapshot commits unconditionally: even a page evicted mid-copy
        yields a valid entry for its key.  Returns the number of pages
        staged."""
        if self._host_kv is None:
            return 0
        hbm_pressure = 0.0
        if not force:
            hbm_pressure = max(
                (row["utilization"]
                 for row in _profiling.poll_device_memory()), default=0.0)
        with self._demote_mutex:
            with self._lock:
                total = self.num_pages - 1
                if not force and total:
                    pressure = max(
                        1.0 - len(self._free_pages) / total, hbm_pressure)
                    if pressure <= 1.0 - self.demote_watermark:
                        return 0
                cands = []
                for key, parent, page, ntok, tokens \
                        in self._prefix.lru_entries():
                    if not bool(self._page_cached[page]) \
                            or key in self._host_kv:
                        continue
                    cands.append((key, parent, page, ntok, tokens))
                    if len(cands) >= self.demote_batch:
                        break
                if not cands:
                    return 0
                # fixed-shape batch (ONE compiled gather program ever):
                # pad with the trash page, discard the padded outputs
                pages_arr = np.zeros(self.demote_batch, np.int32)
                for i, c in enumerate(cands):
                    pages_arr[i] = c[2]
                gathered = self._get_gather()(self.caches, pages_arr)
            # the blocking device->host transfer, OUTSIDE the engine lock
            host = [tuple(np.asarray(x) for x in lt) for lt in gathered]
            staged_blocks = [
                [tuple(np.ascontiguousarray(x[i]) for x in lt)
                 for lt in host]
                for i in range(len(cands))]
            with self._lock:
                staged = 0
                for (key, parent, page, ntok, tokens), blocks \
                        in zip(cands, staged_blocks):
                    if self._host_kv.put(key, parent, ntok, tokens, blocks):
                        staged += 1
                self._kv_demotions += staged
                _M_KV_DEMOTIONS.inc(staged)
                _M_KV_HOST_BYTES.set(self._host_kv.host_bytes)
        if staged:
            _flight.record_event("kv_demote", pages=int(staged))
        return staged

    def _demote_loop(self):
        """Background demotion worker (started with the pump): polls the
        watermark off the tick critical path.  A dying worker degrades to
        no-demotion serving — it never takes the engine down."""
        while not self._stop:
            try:
                self.demote_step()
            except Exception as e:  # pragma: no cover - defensive
                _flight.record_event("demote_worker_error", error=repr(e))
                return
            time.sleep(0.01)

    def _promote_from_tiers(self, req):
        """Re-admit staged (demoted) blocks of ``req``'s prompt: walk the
        prompt's chain keys; blocks missing from the radix index but
        present in the host/disk tier are uploaded back to freshly
        allocated pages in ONE batched scatter program and re-enter the
        index under their original keys — the normal match that follows
        sees them exactly as if they had never been evicted, so chunked
        prefill starts at the first truly-uncached token.  Free-list-only
        allocation: promotion never evicts (a demote<->promote thrash
        cycle would cost more than the re-prefill it saves).  A
        quarantined/lost entry truncates the chain there — the remainder
        re-prefills, corrupt kv is never served.  Returns pages promoted.
        """
        prompt = np.asarray(req.prompt, np.int32)
        usable = int(prompt.size) - 1
        ps = self.ps
        from .prefix_cache import _root_key, chained_block_key

        key, pos, plan = _root_key(req.adapter_id), 0, []
        # a full block's key is computable whenever the prompt HOLDS all ps
        # tokens — even when the n-1 logits cap makes only part of it
        # matchable (match() partially uses a resident full node the same
        # way), so walk to prompt.size and credit the usable part
        while pos + ps <= int(prompt.size):
            k = chained_block_key(key, prompt[pos:pos + ps].tobytes())
            if self._prefix.node_info(k) is None:
                if k not in self._host_kv:
                    break
                plan.append((k, key, min(ps, usable - pos)))
            key = k
            pos += ps
        if pos < usable:
            # partial tail under the chain point: the longest-common-prefix
            # winner, same selection rule as PrefixCache.match
            best, best_t = None, 0
            for pk, ntok, toks in self._host_kv.partial_candidates(key):
                if self._prefix.node_info(pk) is not None:
                    continue  # already resident: match uses it directly
                t_max = min(int(ntok), usable - pos)
                if t_max <= 0:
                    continue
                toks = np.asarray(toks, np.int32)
                eq = toks[:t_max] == prompt[pos:pos + t_max]
                t = t_max if eq.all() else int(np.argmin(eq))
                if t > best_t:
                    best, best_t = pk, t
            if best is not None:
                plan.append((best, key, best_t))
        plan = plan[:len(self._free_pages)]
        if not plan:
            return 0
        t0 = time.perf_counter()
        entries = []
        for k, parent, credit in plan:
            e = self._host_kv.get(k)
            if e is None:
                break  # quarantined mid-chain: children are unreachable
            entries.append((k, parent, credit, e))
        if not entries:
            return 0
        n = len(entries)
        B = 1 << (n - 1).bit_length()  # pow-2 buckets bound retraces
        popped = [self._free_pages.pop() for _ in range(n)]
        pages_arr = np.zeros(B, np.int32)  # padding targets the trash page
        pages_arr[:n] = popped
        first = entries[0][3].blocks
        blocks = [
            tuple(np.stack([e.blocks[li][j] for (_k, _p, _c, e) in entries]
                           + [np.zeros_like(first[li][j])] * (B - n))
                  for j in range(len(first[li])))
            for li in range(len(first))]
        try:
            self.caches = self._get_upload()(self.caches, pages_arr, blocks)
        except Exception:
            # the upload donates self.caches; the pump's _caches_alive
            # check escalates a consumed-buffer failure to the watchdog
            self._free_pages.extend(reversed(popped))
            raise
        tier_tok = {"host": 0, "disk": 0}
        for (k, parent, credit, e), page in zip(entries, popped):
            self._page_ref[page] = 1
            self._page_cached[page] = True
            self._prefix.readmit(k, parent, page, e.ntok, e.tokens)
            tier_tok[e.tier] += int(credit)
        self._prefix_epoch += 1
        self._kv_promotions += n
        _M_KV_PROMOTIONS.inc(n)
        for tier, tok in tier_tok.items():
            if tok:
                _M_TIER_HITS.labels(tier=tier).inc(tok)
                self._tier_hit_tokens[tier] += tok
        req.tier_hit_tokens += sum(tier_tok.values())
        dur = time.perf_counter() - t0
        _M_KV_PROMOTE_S.observe(dur)
        _slo.track("llm_promote", dur)
        _flight.record_event(
            "kv_promote", pages=n, host_tokens=tier_tok["host"],
            disk_tokens=tier_tok["disk"], **_trace_kv(req))
        return n

    def _tier_snapshot(self):
        """stats()/`/varz` "tiers" block — lock-free single reads, same
        contract as stats(); None when the tiers are off (absent-not-zero
        for pre-tier replicas and configs)."""
        if self._host_kv is None:
            return None
        hk = self._host_kv.stats()
        pt = self._prefix_prompt_tokens
        hits = dict(self._tier_hit_tokens)
        return {
            "host": {"entries": hk["host_entries"],
                     "capacity": hk["host_pages"],
                     "bytes": hk["host_bytes"],
                     "hit_tokens": hits["host"],
                     "hit_ratio": hits["host"] / pt if pt else 0.0},
            "disk": {"entries": hk["disk_entries"],
                     "capacity": hk["disk_pages"],
                     "loads": hk["disk_loads"],
                     "quarantined": hk["quarantined"],
                     "hit_tokens": hits["disk"],
                     "hit_ratio": hits["disk"] / pt if pt else 0.0},
            "hbm_hit_tokens": hits["hbm"],
            "demotions": self._kv_demotions,
            "promotions": self._kv_promotions,
            "spilled_to_disk": hk["demotions_to_disk"],
            "dropped": hk["dropped"],
        }

    def _lora_args(self, pages):
        """(lora_tree, lora_rows) tail for the compiled programs.
        The tree is the pool's live device arrays (a jit ARGUMENT —
        loading/evicting adapters swaps data, never the program) and
        ``pages`` the per-row pool pages (0 = the reserved zero adapter:
        its epilogue contributes exact zeros), a HOST array.  Dummies
        (built once, in __init__) keep the call signature stable when the
        engine has no adapter pool."""
        if self.adapters is None:
            return self._no_lora
        return self.adapters.pool.tree(), np.asarray(pages, np.int32)

    def _release_adapter(self, req):
        """Drop a request's adapter-pool reference (idempotent: requests
        that never acquired — queued, base-model — hold page 0).
        Called on every terminal/requeue path, mirroring _release_pages;
        a preempted request re-acquires at re-admission."""
        if req is not None and req.adapter_page:
            self.adapters.release(req.adapter_id)
            req.adapter_page = 0

    def _req_for_slot(self, slot):
        """The request currently writing through ``slot`` — active, or
        the one mid-chunked-prefill (its slot_req entry is still None)."""
        r = self.slot_req[slot]
        if r is None and self._prefilling is not None \
                and self._prefilling[1] == slot:
            return self._prefilling[0]
        return r

    def _cache_insert(self, slot, prompt, trace_id=None, adapter_id=None):
        """Register a freshly prefilled prompt's pages in the prefix index;
        the index's new holds are incref'd so they outlive the slot.
        ``trace_id`` stamps the newly held pages' COW-fork provenance —
        a later request admitted over them links back to this donor.
        ``adapter_id`` seeds the hash chain: kv computed under one adapter
        is only ever matched by requests for the same adapter."""
        if self._prefix is None:
            return
        new_holds = self._prefix.insert(prompt, self._slot_pages[slot],
                                        adapter_id=adapter_id)
        if new_holds:
            self._prefix_epoch += 1
        for page in new_holds:
            self._incref(page)
            self._page_cached[page] = True
            if trace_id:
                self._page_donor[page] = trace_id

    def _slot_held_pages(self):
        """Pages mapped by at least one SLOT (a page held only by the
        prefix cache is reclaimable on demand, so it does not count as in
        use — the capacity gauges would otherwise read a full pool forever
        once the cache warms up)."""
        return int((self._page_ref > self._page_cached).sum())

    def _update_page_gauges(self):
        total = self.num_pages - 1
        used = self._slot_held_pages()
        _M_PAGES_IN_USE.set(used)
        _M_PAGE_UTIL.set(used / total if total else 0.0)
        if self._prefix is not None:
            _M_PAGES_SHARED.set(int((self._page_ref > 1).sum()))
            if self._prefix_prompt_tokens:
                _M_PREFIX_HIT_RATIO.set(
                    self._prefix_hit_tokens / self._prefix_prompt_tokens)

    def _preempt_slot(self, slot, origin="decode"):
        """Preempt an in-flight request whose next token has no free page:
        reclaim its pages and REQUEUE it (recompute-style preemption) — the
        prompt is extended with the tokens generated so far, so
        re-admission re-prefills the full prefix and greedy decoding
        continues exactly where it left off.  A request already holding the
        entire pool can never fit and fails with ServerOverloadedError
        instead of looping forever.  ``origin`` labels the recompute
        counter: ``"verify"`` when the pool ran dry growing the K+1
        verify ladder (mid-verify requeue), ``"decode"`` otherwise."""
        held = len(self._slot_pages[slot])
        req = self._vacate(slot)
        _M_PAGE_PREEMPT.inc()
        _flight.record_event("page_preemption", slot=int(slot),
                             pages_held=int(held),
                             **(_trace_kv(req) if req is not None else {}))
        if req is None:
            return
        self._flush_decode_span(req)
        if held >= self.num_pages - 1 and self._prefix is None:
            # without sharing, a slot mapping the whole pool can never fit;
            # with the prefix cache, `held` counts shared pages too, so the
            # impossibility check moves to re-admission (full private need)
            _fail_future(req.future, ServerOverloadedError(
                f"request needs more kv pages than the whole pool "
                f"({self.num_pages - 1} pages x {self.ps} tokens); rejected"))
            self._goodput.count_tokens("shed", int(req.prompt.size))
            self._end_trace(req, "shed", reason="pool_exhausted",
                            pages_held=int(held))
            return
        req.skip_cache = True
        req.requeue_reason = "page_pool_dry"
        req.trace.inc_attr("preempt_requeues")
        # only the tokens generated since the last requeue: the earlier
        # ones are in the prompt already (a second preemption that appended
        # them all again would resume from a context with them doubled)
        req.prompt = np.concatenate(
            [req.prompt, np.asarray(req.tokens[req.regrown:], np.int32)])
        req.regrown = len(req.tokens)
        # every token of the extended prompt (original prompt + generated
        # so far) must be re-prefilled from scratch — preemption's token
        # bill, on the registry counter and the goodput token ledger
        recompute = int(req.prompt.size)
        _M_RECOMPUTE_TOKENS.labels(
            reason="mid_verify" if origin == "verify"
            else "page_pool_dry").inc(recompute)
        self._recompute_tokens += recompute
        self._goodput.count_tokens("preempt_recomputed", recompute)
        with self._pending.mutex:
            self._pending.queue.appendleft(req)

    def _ensure_decode_pages(self, active, eff, origin="decode"):
        """Grow each active slot's page table to cover the rows this tick
        will write (pos .. pos+eff-1), COW-forking any of those pages that
        are shared; preempt slots the pool cannot cover.  Returns the
        surviving active list — or None, having preempted nothing, where a
        slot cannot be covered while a decode result is in flight:
        _preempt_slot regrows the prompt from req.tokens, so the caller
        reads that result first and asks again (what was grown so far stays
        with its slot)."""
        out = []
        for i in active:
            pos = int(self.slot_pos[i]) + int(self._ahead[i])
            first = pos // self.ps
            last = (pos + eff - 1) // self.ps
            ok = self._alloc_pages(i, last + 1 - len(self._slot_pages[i]))
            if ok and self._prefix is not None:
                # only the boundary page can be shared (grown pages are
                # fresh), but the per-entry refcount check is O(1)
                for idx in range(first, last + 1):
                    if not self._cow_page(i, idx):
                        ok = False
                        break
            if ok:
                out.append(i)
            elif self._inflight is not None:
                return None
            else:
                self._preempt_slot(i, origin=origin)
        return out

    def _chunk_prefill_fn(self):
        """ONE compiled program prefills any prompt in fixed-size chunks —
        ids [1, C] against the paged pools at per-slot offset `off`, so no
        prompt length compiles anything.  On tile-aligned
        shapes the chunk's attention is the RAGGED paged Pallas kernel
        (the chunk offset rides the kernel's prefetched lengths;
        llm_attn_kernel_total counts the dispatch).  Returns the logits at
        `last_index` (the final chunk's last real token) and the updated
        pools; the page table row routes the scatter, padded tail rows land
        in the trash page / are overwritten by the first decode."""
        model = self.model
        pool = self.adapters.pool if self.adapters is not None else None

        kinds = self._cache_kinds

        def llm_prefill_chunk(params, buffers, caches, page_row, ids, off,
                              last_index, lora_tree, lora_rows, *slot_and_acc):
            # a model that declares its layers' kinds also gets the chunk's
            # SLOT: recurrent state is a slot's, zero where the request's
            # first chunk runs (off == 0, so a requeued request recomputes
            # from nothing), carried from chunk to chunk, and not advanced
            # by the padded tail past last_index
            rows = None if kinds is None else _SlotRows(
                slot_and_acc[0].reshape(1), off == 0,
                (last_index + 1).reshape(1))
            restore = model.bind_functional_state(params, buffers)
            try:
                with tape.no_grad(), _lora_ctx(pool, lora_tree, lora_rows):
                    logits, new_caches = model.prefill_chunk_step(
                        Tensor(ids),
                        _to_model_caches(kinds, caches, off, page_row, rows),
                        last_index)
                    raw, aux = _from_model_caches(kinds, new_caches)
            finally:
                restore()
            # the layers' counts go to the accumulators' prefill plane
            return (logits._value, raw) + tuple(
                a.at[1].add(x) for a, x in zip(slot_and_acc[1:], aux))

        return jax.jit(llm_prefill_chunk, donate_argnums=(2,) + tuple(
            range(10, 10 + len(self._accs()))))

    def _get_chunk_prefill(self):
        """The one program that carries a chunk: llm_mixed where the
        engine takes a chunk and the decode rows through one weight pass,
        else llm_prefill_chunk."""
        if self._chunk_jit is None:
            _profiling.record_compile("chunk_prefill")
            self._chunk_jit = _mixed_program(self.model, self._cache_kinds) \
                if self._mixed else self._chunk_prefill_fn()
        return self._chunk_jit

    def _accs(self):
        """The device accumulators the programs add to and hand back, in
        their fixed order: the expert layers' pairs, the block-sparse
        layers' blocks, the latent layers' context tokens (each only with
        such layers)."""
        return tuple(a for a in (self._moe_acc, self._sparse_acc,
                                 self._latent_acc) if a is not None)

    def _chunk_extra(self, slot):
        """The chunk program's trailing arguments for a model that declares
        its layers' cache kinds: the slot, and the accumulators."""
        if self._cache_kinds is None:
            return ()
        return (np.int32(slot),) + self._accs()

    def _took(self, out):
        """Keep what a decode or chunk program hands back — the caches and
        the accumulators; returns its first result."""
        first, self.caches = out[:2]
        rest = list(out[2:])
        if self._moe_acc is not None:
            self._moe_acc = rest.pop(0)
        if self._sparse_acc is not None:
            self._sparse_acc = rest.pop(0)
        if self._latent_acc is not None:
            self._latent_acc = rest.pop(0)
        return first

    def _took_decode(self, out):
        """_took for the decode program, whose last result is the token
        feed of the next one; returns its tokens, still on the device."""
        self._feed = out[-1]
        return self._took(out[:-1])

    def _took_mixed(self, out):
        """_took_decode for llm_mixed; returns (its tokens, the chunk's
        logits), both still on the device."""
        return self._took_decode(out[:2] + out[3:]), out[2]

    def _moe_dispatched(self, program, rows, calls):
        """`rows` real rows went through every expert layer `calls` times."""
        n = len(self._moe_kinds)
        self._moe_pending[_MOE_PROGRAMS.index(program)] += (
            rows * calls * self._moe_kinds[0].top_k * n, calls * n)

    def _publish_moe(self, total, pending):
        """`total`: the device accumulator as a decode program brought it
        back, flat; `pending`: the (pairs, layer calls) by program that were
        dispatched between the decode program read before it and this one
        (_InFlight.moe), i.e. what `total` has gained since.  Publishes
        that: the counters, stats()["moe"] and the largest load of the
        tick."""
        total = total.astype(np.uint32).reshape(self._moe_seen.shape)
        delta = (total - self._moe_seen).astype(np.int64)  # wraps like int32
        self._moe_seen = total
        for i, prog in enumerate(_MOE_PROGRAMS):
            pairs, calls = (int(x) for x in pending[i])
            if not calls:
                continue
            held = int(delta[i, :, :-1].sum())
            touched = int(delta[i, :, -1].sum())
            _M_MOE_PAIRS.labels(where="held", program=prog).inc(held)
            _M_MOE_PAIRS.labels(where="absent", program=prog).inc(pairs - held)
            _M_MOE_TOUCHED.labels(program=prog).inc(touched)
            _M_MOE_CALLS.labels(program=prog).inc(calls)
            st = self._moe_stats[prog]
            st["pairs_held"] += held
            st["pairs_absent"] += pairs - held
            st["experts_touched"] += touched
            st["layer_calls"] += calls
        self._moe_max_load = int(delta[0, :, :-1].max())
        _M_MOE_MAX_LOAD.set(self._moe_max_load)

    def _publish_counts(self, counts):
        """The attention layers' accumulators as the decode tick brought
        them back behind the experts', flat and in _accs() order: publishes
        what both programs added to each since."""
        groups = []
        if self._sparse_acc is not None:
            groups.append((self._sparse_seen, self._sparse_stats,
                           _SPARSE_FIELDS, _M_SPARSE_SELECTED))
        if self._latent_acc is not None:
            groups.append((self._latent_seen, self._latent_stats,
                           _LATENT_FIELDS, _M_LATENT_CONTEXT))
        at = 0 if self._moe_acc is None else self._moe_seen.size
        for seen, stats, fields, series in groups:
            total = counts[at:at + seen.size].astype(np.uint32).reshape(seen.shape)
            at += seen.size
            delta = (total - seen).astype(np.int64)  # wraps like int32
            seen[...] = total
            for i, prog in enumerate(_MOE_PROGRAMS):
                for f, d in zip(fields, delta[i]):
                    stats[prog][f] += int(d)
                # the series counts the last field: what the layers read
                series.labels(program=prog).inc(int(delta[i, -1]))

    def _admit_paged(self):
        """Chunked-prefill admission: at most ONE prompt chunk per tick, so
        running slots keep decoding underneath a long admission (the
        head-of-line fix).  Admission is gated on FREE PAGES: the queue head
        waits until reclamation frees enough pages for its prompt + first
        decode token."""
        if self._prefilling is None:
            self._start_prefill()
        elif not self._pending.empty():
            self._blocked("prefill_busy")
        if self._prefilling is not None:
            self._prefill_tick()

    def _pop_admission_request(self):
        """Pop the next request to admit.  FIFO by default; with
        ``cache_aware_admission``, scan the first few queued requests and
        pick the one with the LONGEST cached prompt prefix (strict FIFO
        on ties), reusing each request's memoized radix match.  Fairness:
        passed-over requests age by one ``adm_skips`` per bypass; once
        the queue head hits ``admission_age_cap`` it admits next
        regardless of cache affinity, so a cold request starves for a
        bounded number of admissions only."""
        if not self.cache_aware:
            try:
                return self._pending.get_nowait()
            except queue.Empty:
                return None
        with self._pending.mutex:
            q = self._pending.queue
            if not q:
                return None
            best, best_hit = 0, -1
            if q[0].adm_skips < self.admission_age_cap:
                for idx in range(min(len(q), 8)):
                    r = q[idx]
                    hit = 0
                    if not r.skip_cache:
                        if r.match_epoch != self._prefix_epoch \
                                or r.match_result is None:
                            r.match_result = self._prefix.match(
                                r.prompt, adapter_id=r.adapter_id)
                            r.match_epoch = self._prefix_epoch
                        hit = r.match_result[0]
                    if hit > best_hit:
                        best, best_hit = idx, hit
            req = q[best]
            del q[best]
            if best:
                for idx in range(best):
                    q[idx].adm_skips += 1
                _M_ADM_REORDERS.inc()
                self._adm_reorders += 1
            self._pending.not_full.notify()
            return req

    def _start_prefill(self):
        free = [i for i, r in enumerate(self.slot_req) if r is None]
        if not free and not self._pending.empty():
            self._blocked("no_slot")
        while free and not self._pending.empty():
            # _adm_inflight (incremented BEFORE the pop) covers the window
            # where the request is out of the queue but not yet in
            # _prefilling, requeued, or terminal — drain()'s _drained(),
            # read from another thread, must never observe a
            # momentarily-empty engine mid-admission
            self._adm_inflight += 1
            try:
                req = self._pop_admission_request()
                if req is None:
                    return
                if req.future.done():
                    # cancelled / failed by a pump-death race
                    self._end_trace(req, "cancelled")
                    continue
                if req.deadline is not None \
                        and self._clock() > req.deadline:
                    _M_EXPIRED.labels(where="queued").inc()
                    _fail_future(req.future, DeadlineExceededError(
                        "request deadline expired while queued for "
                        "admission"))
                    self._end_trace(req, "expired", where="queued")
                    continue
                need = -(-(req.prompt.size + 1) // self.ps)
                if self._host_kv is not None and not req.skip_cache \
                        and len(self._host_kv):
                    # hierarchical tiers: re-upload demoted blocks FIRST;
                    # a promotion bumps the prefix epoch, so the match
                    # below re-runs against the readmitted nodes
                    self._promote_from_tiers(req)
                matched, shared = 0, []
                if self._prefix is not None and not req.skip_cache:
                    if req.match_epoch == self._prefix_epoch \
                            and req.match_result is not None:
                        # head-of-line request spinning on a full pool: the
                        # index hasn't changed, don't re-hash the prompt's
                        # blocks every tick
                        matched, shared = req.match_result
                    else:
                        matched, shared = self._prefix.match(
                            req.prompt, adapter_id=req.adapter_id)
                        req.match_epoch = self._prefix_epoch
                        req.match_result = (matched, shared)
                if need > self.num_pages - 1:
                    # TOTAL need, not unique: a cached prefix's pages
                    # occupy the same pool, so a slot whose table must
                    # reference more pages than exist can never complete —
                    # admitting it would spin head-of-line forever (its
                    # own matched pages pin the cache against eviction)
                    _fail_future(req.future, ServerOverloadedError(
                        f"prompt needs {need} kv pages but the pool only "
                        f"has {self.num_pages - 1}; rejected"))
                    self._goodput.count_tokens("shed", int(req.prompt.size))
                    self._end_trace(req, "shed", reason="pool_too_small",
                                    pages_needed=int(need))
                    continue
                slot = free[0]
                if shared:
                    # map the cached prefix straight into the slot's
                    # table; admission below is charged only for the
                    # UNIQUE pages
                    for p in shared:
                        self._incref(p)
                    self._slot_pages[slot] = list(shared)
                    self._pt_host[slot, :len(shared)] = shared
                if not self._alloc_pages(slot, need - len(shared)):
                    # admission by free pages: head-of-line waits for
                    # reclamation (put it back where it came from; the
                    # shared holds roll back so the cache stays evictable
                    # meanwhile)
                    self._release_pages(slot)
                    with self._pending.mutex:
                        self._pending.queue.appendleft(req)
                    self._blocked("no_pages")
                    return
                if req.adapter_id is not None and not req.adapter_page:
                    page = self.adapters.acquire(req.adapter_id)
                    if page is None:
                        # adapter pool dry (every page pinned by live
                        # requests): wait at the head for a release,
                        # exactly like the kv-page wait above — roll the
                        # kv holds back so the pool stays reclaimable
                        self._release_pages(slot)
                        with self._pending.mutex:
                            self._pending.queue.appendleft(req)
                        self._blocked("no_adapter_page")
                        return
                    req.adapter_page = page
                # first admission EVER (admit_ts is stamped once and
                # survives requeues): preemption/COW-starvation retries
                # must not observe queue-wait twice nor double-count the
                # hit-ratio denominator
                first_admission = req.admit_ts is None
                req.admit_ts = self._clock()
                if req.submit_ts is not None and first_admission:
                    self._trace_queue_wait(req)
                    self._prefix_prompt_tokens += int(req.prompt.size)
                    self._prefix_hit_tokens += int(matched)
                    req.hit_tokens = int(matched)  # reversed if the
                    # prefill is abandoned by a COW-starvation requeue
                    # (the skipped chunks get recomputed privately, so the
                    # hit never happened)
                    if self._host_kv is not None:
                        # tier attribution: whatever the promotion above
                        # did not supply was already HBM-resident
                        hbm = max(0, int(matched) - req.tier_hit_tokens)
                        if hbm:
                            _M_TIER_HITS.labels(tier="hbm").inc(hbm)
                            self._tier_hit_tokens["hbm"] += hbm
                # COW-fork provenance: the deepest shared page's donor
                # trace links this admission back to the request whose
                # prefill populated the prefix (rendered by /tracez as a
                # cross-trace link)
                donor = None
                for p in reversed(shared):
                    d = self._page_donor.get(p)
                    if d and d != req.trace.trace_id:
                        donor = d
                        break
                if donor:
                    self._open_admission_span(
                        req, slot, cached_tokens=int(matched),
                        prefix_donor=donor)
                else:
                    self._open_admission_span(req, slot,
                                              cached_tokens=int(matched))
                if matched and self._ckpt is not None:
                    # the state at the shared prefix's end, in place of the
                    # zeroing a fresh request gets (its first chunk runs at
                    # off = matched > 0, so SlotRows.fresh is false)
                    self.caches = self._get_ckpt_load()(
                        self.caches, self._ckpt, np.int32(slot),
                        np.int32(self._prefix.checkpoint_of(shared[-1])))
                    self._ckpt_event("restored")
                # chunked prefill starts at the first UNCACHED token — a
                # hit skips every chunk the cache already covers
                req.prefill_from = int(matched)
                self._prefilling = (req, slot, matched)
                return
            finally:
                self._adm_inflight -= 1

    def _prefill_tick(self):
        """The admitting request's next chunk: check it (_check_chunk),
        stage it (_stage_chunk), and either dispatch llm_prefill_chunk now
        — a final chunk's logits then wait in _chunk_out for the end of
        this tick (_first_token), after its decode program is dispatched —
        or, where one program takes a chunk and the decode rows through
        one weight pass, leave it staged for _decode_tick, which dispatches
        llm_mixed with it."""
        self._phases.switch("prefill_stage")
        if not self._check_chunk():
            return
        chunk = self._stage_chunk()
        if self._mixed:
            self._staged = chunk
            return
        args = (self._params, self._buffers, self.caches, *chunk.args,
                *self._lora_args([chunk.req.adapter_page]),
                *self._chunk_extra(chunk.slot))
        logits = self._dispatch_chunk(chunk, args)
        if logits is not None and chunk.final:
            # _prefilling stays set until the slot is active (_first_token,
            # later in this tick)
            self._chunk_out = (chunk.req, chunk.slot, logits)

    def _check_chunk(self):
        """Whether the admitting request's next chunk may run: not when the
        request was cancelled or its deadline has passed (it is failed and
        its pages go back), nor when the chunk would write into a page
        other slots still read and no page can be freed for the fork (it is
        requeued to prefill privately)."""
        req, slot, done = self._prefilling
        if req.future.done() or (req.deadline is not None
                                 and self._clock() > req.deadline):
            self._prefilling = None
            self._release_pages(slot)
            self._release_adapter(req)
            if not req.future.done():
                _M_EXPIRED.labels(where="inflight").inc()
                _fail_future(req.future, DeadlineExceededError(
                    f"request deadline exceeded after {done} prefilled "
                    "prompt tokens"))
                self._end_trace(req, "expired", where="prefill",
                                prefilled_tokens=int(done))
            else:
                self._end_trace(req, "cancelled")
            return False
        if self._prefix is None or self._cow_page(slot, done // self.ps):
            return True
        # requeue recompute-style (fully private next time) instead of
        # wedging or failing
        self._release_pages(slot)
        self._release_adapter(req)
        req.skip_cache = True
        # the hit credited at admission never materialized: the private
        # re-prefill recomputes every chunk the cache was covering
        self._prefix_hit_tokens -= req.hit_tokens
        req.hit_tokens = 0
        _M_PAGE_PREEMPT.inc()
        _flight.record_event("page_preemption", slot=int(slot),
                             where="prefill_cow", **_trace_kv(req))
        if req.adm_span is not None:
            req.adm_span.close(error="cow_starved")
            req.adm_span = None
        req.requeue_reason = "prefill_cow"
        req.trace.inc_attr("preempt_requeues")
        # the whole prompt re-prefills privately next episode — the
        # chunks already written AND the cache-hit tokens just
        # un-credited are all recomputed
        recompute = int(req.prompt.size)
        _M_RECOMPUTE_TOKENS.labels(reason="prefill_cow").inc(recompute)
        self._recompute_tokens += recompute
        self._goodput.count_tokens("preempt_recomputed", recompute)
        with self._pending.mutex:
            self._pending.queue.appendleft(req)
        # clear the marker only after the requeue is visible, so
        # drain()'s lock-free _drained() never sees an empty queue
        # with the request parked nowhere
        self._prefilling = None
        return False

    def _stage_chunk(self):
        """The admitting request's next chunk with what a program takes for
        it: the slot's page-table row (forked where it had to be, and not
        touched again before the dispatch), the ids padded to the program's
        width, the tokens already prefilled, the index of the last real
        token.  Host arrays straight into the compiled call: slicing or
        casting on the device here would be eager ops that compile after
        warmup()."""
        req, slot, done = self._prefilling
        if self._recurrent and done > req.prefill_from:
            # the chunk resumes the state this prompt's last chunk left
            self._carried_chunks += 1
            _M_STATE_CARRIED.inc()
        m = min(self.prefill_chunk, req.prompt.size - done)
        ids = np.full((1, self.prefill_chunk), self.pad, np.int32)
        ids[0, :m] = req.prompt[done:done + m]
        return _Chunk(req, slot, done, m, (
            self._pt_host[slot:slot + 1].copy(), ids,
            np.full((1,), done, np.int32), np.int32(m - 1)))

    def _dispatch_chunk(self, chunk, args):
        """Call the program that carries ``chunk`` and keep its results
        (_took, or _took_mixed for llm_mixed).  The call is asynchronous:
        the prefill_dispatch phase, like the llm_prefill_chunk span inside
        it, is the host's time to DISPATCH; the wait shows where the host
        next reads a result dispatched with or after it.  Books the chunk —
        its count, its goodput seconds, the request's progress; after its
        last, the prompt's pages go into the prefix index — and returns
        what _took / _took_mixed returned; ``None`` where the call failed (that
        request alone fails; a failure that took the donated caches with
        it is raised, to the pump's watchdog)."""
        req, slot = chunk.req, chunk.slot
        pc = self._phases
        t_pf = pc.switch("prefill_dispatch")
        try:
            jit = self._get_chunk_prefill()
            if _obs.enabled():
                with _span("llm_prefill_chunk", _M_PREFILL_CHUNK_S,
                           trace=req.trace,
                           attrs={"index": chunk.done // self.prefill_chunk,
                                  "tokens": int(chunk.m)}):
                    out = jit(*args)
            else:
                out = jit(*args)
            out = self._took_mixed(out) if self._mixed else self._took(out)
            if self._moe_acc is not None:
                self._moe_dispatched("prefill", chunk.m, 1)
        except Exception as e:
            self._prefilling = None
            self._release_pages(slot)
            self._release_adapter(req)
            _fail_future(req.future, e)
            self._end_trace(req, "error", error=repr(e))
            if not self._caches_alive():
                # the call DONATES self.caches: an execution-time
                # failure may have consumed the buffers, and serving on
                # deleted arrays would fail every later request with a
                # misleading error — escalate to the pump watchdog instead
                raise
            return None
        # goodput ledger: a first-episode chunk is productive prefill; a
        # re-admission (adm_episode > 1: page-pool-dry, mid-verify or
        # COW-starved requeue) recomputes kv it already computed once.
        # Its seconds are the prefill_dispatch phase's, boundary for boundary
        self._goodput.carve(
            "preempt_recompute_waste" if req.adm_episode > 1 else "prefill",
            pc.switch("bookkeep") - t_pf)
        _M_PREFILL_CHUNKS.inc()
        if not chunk.final:
            self._prefilling = (req, slot, chunk.done + chunk.m)
            return out
        # the slot's pages now hold the whole prompt's kv: index the full
        # blocks + partial tail so CONCURRENT same-prefix requests hit
        # (insert precedes the first decode write, whose COW check then
        # sees the tail page as shared and forks it; a stateful index takes
        # whole-page prompts alone, which a checkpoint then follows)
        self._cache_insert(slot, req.prompt, trace_id=req.trace.trace_id,
                           adapter_id=req.adapter_id)
        if self._ckpt is not None:
            self._ckpt_store(slot, req)
        return out

    def _first_token(self, req, slot, logits):
        """A final chunk's last step: read its logits, select the first
        token on the host and activate the slot, which joins the NEXT
        decode program with this token from the host.  WHEN depends on the
        program that carried the chunk.  llm_prefill_chunk: at the end of
        the tick that dispatched it, once that tick's decode program is
        dispatched behind it and the one before is booked — the read waits
        for the chunk while the decode program queued behind it keeps the
        device busy.  llm_mixed: where that program is booked (_book), a
        tick later and after the next program is dispatched — the logits
        are there with the program's tokens, the read is a transfer, and
        an admission never makes the pump read before it dispatches."""
        self._phases.switch("first_token_sync")
        tok = self._host_select(np.asarray(logits)[0, 0], req)
        first = not req.tokens  # re-admission after preemption continues
        req.slot = slot
        self._emit_token(req, tok, time.perf_counter())
        # the final prefill chunk emits one token (both on first admission
        # and on a post-preemption re-admission): useful either way
        self._goodput.count_tokens("useful", 1)
        self.slot_req[slot] = req
        self.slot_pos[slot] = req.prompt.size
        self.last_token[slot] = tok
        _M_ADMITTED.inc()
        self._first_token_out(req, first)
        if tok == self.eos or len(req.tokens) >= req.max_new_tokens:
            self._finish(slot)
        self._phases.switch("bookkeep")

    def program_census(self):
        """``{module: {instruction: row}}`` of every program the engine
        runs (``distributed.census.per_op_census`` rows: opcode,
        computation, named scope, bytes, flops), keyed by the module name a
        device prints (``jit_llm_decode``): what
        ``observability.xplane.device_seconds`` joins a profile of this
        engine against.

        Built on the first call and kept.  Each program of ``_programs()``
        (and the tier gather, once the demotion worker has compiled it) is
        lowered for the arguments a tick gives it and compiled to be read
        — seconds a program (a lowering traces the model and its Pallas
        kernels again; the compile is keyed WITH its metadata, so the
        first census of a code version compiles anew), so never from
        ``__init__``, ``warmup()`` or a tick, and nothing of it goes into
        ``stats()``.  The arguments are taken under the engine lock as
        shapes; the lowering runs outside it, with the pump running.  The
        tier upload's programs (one a batch bucket) are not in it: their
        module shows under ``other_programs``."""
        from ..distributed import census as _census

        with self._census_lock:
            if self._census is not None:
                return self._census

            def shape(x):
                if not isinstance(x, jax.Array):
                    return x
                return jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding,
                    weak_type=getattr(x, "weak_type", False))

            with self._lock:
                programs = [(jit, jax.tree_util.tree_map(shape, args))
                            for jit, args, _ in self._programs()]
                if self._gather_jit is not None:
                    programs.append((self._gather_jit, (
                        jax.tree_util.tree_map(shape, self.caches),
                        np.zeros(self.demote_batch, np.int32))))
            # a persistent compile cache keys programs without their
            # metadata: a hit would print the scopes of whatever code first
            # compiled the program.  The pump compiles nothing meanwhile.
            key = "jax_compilation_cache_include_metadata_in_key"
            was = getattr(jax.config, key)
            jax.config.update(key, True)
            try:
                with _profiling.census_compiles():
                    self._census = _census.by_module(*(
                        _census.per_op_census(jit.lower(*args).compile())
                        for jit, args in programs))
            finally:
                jax.config.update(key, was)
            return self._census

    def profile_device(self, seconds):
        """Profile the device for ``seconds`` while the engine runs — from
        any thread, over the running pump — and reduce the dump by the
        engine's census to device seconds by (program, named scope).

        Returns the ``xplane.device_seconds`` result (``None`` where the
        profiler could not run: one is running already, the backend has
        none — the error is recorded, nothing is raised).  The seconds by
        (program, first scope component) of the last window are published
        in ``stats()["device_time"]`` and on ``llm_device_seconds{program,
        scope}``; the top rows are filed as spans of a ``profile_device``
        trace.  The census is built before the window opens (the first
        call pays for it: program_census()); the dump is read after it
        closes and then deleted."""
        import shutil

        census = self.program_census()
        trace = self._tracer.start_trace("profile_device",
                                         seconds=float(seconds))
        with _profiling.ProfilingSession(trace=trace, census=census) as sess:
            time.sleep(seconds)
        trace.end("ok" if sess.error is None else "error")
        shutil.rmtree(sess.logdir, ignore_errors=True)
        red = sess.by_scope
        if red is None:
            self._device_time = {"error": sess.error}
            return None
        table = {}
        for module, prog in red["programs"].items():
            row = table.setdefault(module, {})
            for scope, v in prog["scopes"].items():
                first = scope.split("/")[0]
                row[first] = row.get(first, 0.0) + v["seconds"]
            if prog["unmatched_s"]:
                row["unmatched"] = prog["unmatched_s"]
        for module, row in table.items():
            for scope, secs in row.items():
                _M_DEVICE_SECONDS.labels(program=module, scope=scope).set(secs)
        self._device_time = {
            "error": None, "window_s": red["window_s"],
            "busy_s": red["busy_s"], "seconds": table,
            "unmatched_s": red["unmatched_s"],
            "other_programs": red["other_programs"],
            # what the call itself took: the profiler's start and stop,
            # and reading the dump
            "profiler_s": {"start": sess.start_s, "stop": sess.stop_s,
                           "extract": sess.extract_s}}
        return red

    def _programs(self):
        """Every program warmup() compiles, in its order, as ``(jit,
        arguments, keep)``: the arguments are what a tick gives the
        program — host arrays, the default generator's resident key with a
        host offset in place of keys, and for the decode's token feed the
        resident zeros (one signature with a program's own last tokens:
        both are uncommitted [B] int32 arrays of the one device) — and
        ``keep`` takes the call's results where a tick puts them.
        warmup() calls each; program_census() lowers each and calls
        none, so the program it reads is the one the ticks call.  A
        generator on purpose: a call donates the caches, and the next
        program's arguments are read after ``keep`` has replaced them."""
        def caches(c):
            self.caches = c

        def ckpt(p):
            self._ckpt = p

        C, B = self.prefill_chunk, self.n_slots
        tokens = np.full((B, 1), self.pad, np.int32)
        pos = np.zeros((B,), np.int32)
        knobs = self._sampling_knobs([])  # every row greedy
        rng = (_fr.default_generator().key, np.uint32(0))
        # last_index -1: no token of the warm-up chunk is real, so
        # slot 0's recurrent state and the expert counts stay put
        chunk = (np.zeros((1, self.M), np.int32),
                 np.full((1, C), self.pad, np.int32),
                 np.zeros((1,), np.int32),
                 np.int32(0 if self._cache_kinds is None else -1))
        if self._mixed:
            # llm_mixed in llm_prefill_chunk's place: every row masked
            yield self._get_chunk_prefill(), (
                *self._cache_args([]), tokens, self._feed,
                np.ones((B,), bool), pos, *knobs, self._mask_all_true, *rng,
                *chunk), self._took_mixed
        else:
            yield self._get_chunk_prefill(), (
                self._params, self._buffers, self.caches, *chunk,
                *self._lora_args([0]), *self._chunk_extra(0)), self._took
        if not self._recurrent:
            # the COW fork program too: a warm engine's first
            # shared-prefix fork must not compile (and must not trip
            # recompile_storm).  A trash-page self-copy is harmless.
            # (A model with recurrent state shares whole pages only,
            # and writes to none of them: no fork.)
            yield self._get_cow_copy(), (
                self.caches, np.int32(0), np.int32(0)), caches
        if self._ckpt is not None:
            # the checkpoint copies, both ways, on entry 0 and slot 0:
            # the entry is free and the slot idle, so what they move is
            # never read (a request's first chunk zeroes its state)
            yield self._get_ckpt_store(), (
                self.caches, self._ckpt, np.int32(0), np.int32(0)), ckpt
            yield self._get_ckpt_load(), (
                self.caches, self._ckpt, np.int32(0), np.int32(0)), caches
        eff = max(1, min(self.decode_chunk, self.L - 1))
        lora = self._lora_args([0] * B)
        yield self._get_decode(eff), (
            *self._cache_args(), tokens, self._feed, np.ones((B,), bool),
            pos, *knobs, self._mask_all_true, *rng, *lora,
            *self._accs()), self._took_decode
        if self.spec_k:
            yield self._get_verify(), (
                *self._cache_args(), tokens,
                np.zeros((B, self.spec_k), np.int32), pos, *knobs, *rng,
                *lora), lambda out: caches(out[2])

    def warmup(self):
        """Pre-compile the serving programs so the FIRST request pays no
        compile latency (the TTFT spike visible in llm_ttft_seconds): the
        program that carries a prefill chunk (it serves every prompt length:
        llm_prefill_chunk, or llm_mixed in its place), the COW page
        copy, the decode step at the configured decode_chunk and, with
        ``spec_k``, the verify step.  Runs the real compiled calls against
        the engine's own idle cache state: the garbage rows land in the
        trash page.  The calls get what a tick gives them (_programs), so
        the warmed programs are the ones the ticks call; the offset is
        not advanced (warmup draws nothing a request sees).  Returns the
        wall seconds spent and publishes them on
        llm_warmup_compile_seconds."""
        t0 = time.perf_counter()
        # every tick's span records into the native host-trace buffer, whose
        # library is BUILT on first use in a fresh checkout (a g++ run):
        # resolve it here, not on the pump thread under the first request
        from ..profiler import _tracer

        _tracer()
        with self._lock:
            if self._prefilling is not None \
                    or any(r is not None for r in self.slot_req):
                raise RuntimeError("warmup() requires an idle engine")
            if self._inflight is not None:
                self._read_decode("warmup")  # every row of it has ended
            for jit, args, keep in self._programs():
                keep(jit(*args))
            if self.adapters is not None:
                # the pool's donating page writer compiles here too, so a
                # post-warmup register()/acquire() never counts as a
                # recompile
                self.adapters.warm()
        dt = time.perf_counter() - t0
        _M_WARMUP_S.set(dt)
        # every expected program is now compiled: later compiles are
        # recompiles (jit_recompiles_total -> the recompile_storm rule)
        _profiling.mark_warm()
        return dt

    def _host_select(self, row, req):
        """First (admission) token: host-side mirror of _select_rows, same
        masking order (constraint mask -> temperature -> top-k by VALUE ->
        top-p over the survivors)."""
        if req.cursor is not None:
            row = np.where(req.cursor.mask(), row, -np.inf)
        if not req.do_sample:
            tok = int(row.argmax())
            if req.cursor is not None:
                req.cursor.advance(tok)
                _constrain.count_masked_token()
            return tok
        lt = row.astype(np.float64) / max(req.temperature, 1e-6)
        if 0 < req.top_k < row.size:
            kth = np.sort(lt)[::-1][req.top_k - 1]
            lt = np.where(lt < kth, -np.inf, lt)
        s = np.sort(lt)[::-1]
        e = np.exp(s - s.max())
        cum = np.cumsum(e / e.sum())
        cutoff = s[min(int((cum < req.top_p).sum()), s.size - 1)]
        lt = np.where(lt < cutoff, -np.inf, lt)
        p = np.exp(lt - lt.max())
        tok = int(self._rng.choice(row.size, p=p / p.sum()))
        if req.cursor is not None:
            req.cursor.advance(tok)
            _constrain.count_masked_token()
        return tok

    def _sampling_knobs(self, rows=None):
        """Per-slot (do_sample, temperature, top_k, top_p) as HOST arrays the
        compiled step takes as arguments; ``rows``: the slots the program
        carries (default: every slot with a request), the others are greedy
        like idle ones.  numpy on purpose: a
        jnp.asarray(list, dtype) converts ON DEVICE, an eager op whose first
        use compiles after warmup() has declared the process warm.

        The rule for everything a tick stages: NO eager device call belongs
        between a tick's compiled calls.  Each one is a one-op program the
        host dispatches with the device idle (0.5-0.9 ms apiece on a v5e,
        PERF.md PR 25).  Arguments are host arrays (the compiled call
        uploads them) or arrays already resident on the device; what has to
        be computed per tick — the sampler's keys — is computed inside the
        program from ``Generator.fork()``'s (key, offset)."""
        B = self.n_slots
        do_s, temp = np.zeros(B, bool), np.ones(B, np.float32)
        topk, topp = np.zeros(B, np.int32), np.ones(B, np.float32)
        for i in range(B) if rows is None else rows:
            r = self.slot_req[i]
            if r is not None:
                do_s[i], temp[i] = r.do_sample, r.temperature
                topk[i], topp[i] = r.top_k, r.top_p
        return do_s, temp, topk, topp

    def _count_sampler_tick(self, do_s, topk, topp):
        """Which part of the sampler this tick's knobs engage, by the
        predicate the program branches on (ops/sampling.py): only rows
        that draw count, so a greedy request's stale top_p sorts nothing.
        Counted from the host's own arrays; nothing is read back."""
        if not do_s.any():
            path = "greedy"
        elif (do_s & _has_threshold(topk, topp, self._vocab)).any():
            path = "threshold"
        else:
            path = "draw"
        self._sampler_ticks[path] += 1
        _SAMPLER_SERIES[path].inc()

    def _cache_args(self, rows=None):
        """What the decode and verify programs take first: the weights, the
        caches, and the page table with every slot the program does not
        carry masked to the trash page (``rows``: the slots it carries;
        default: every slot with a request) — a mid-prefill slot already
        owns real pages, and the shared step's garbage scatter for it must
        not clobber the prompt rows the chunked prefill has already
        written; a row that ends with its token in flight must compute
        nothing more."""
        if rows is None:
            rows = [i for i, r in enumerate(self.slot_req) if r is not None]
        pt = np.zeros_like(self._pt_host)
        pt[rows] = self._pt_host[rows]
        return self._params, self._buffers, self.caches, pt

    def _get_decode(self, eff):
        jit = self._decode_jit.get(eff)
        if jit is None:
            _profiling.record_compile("decode")
            jit = self._decode_jit[eff] = self._decode_fn(eff)
        return jit

    def _decode_fn(self, eff):
        """The compiled decode step over ``eff`` tokens a row (static: one
        program per scan length).  Randomness arrives as ``(base_key,
        offset)`` — the default generator's resident key and a host integer
        (``Generator.fork()``) — and the per-token keys are derived HERE, in
        the program: an eager ``jax.random.split`` on the host would be a
        train of one-op device programs before every tick's dispatch.

        A row's input token is picked HERE too: ``tokens`` [B, 1] from the
        host where ``from_host`` says so (a slot activated since the last
        dispatch, any row after a drain), else ``feed`` [B], the last
        tokens of the program dispatched before this one, which never left
        the device — so the pump can dispatch this program before it has
        read that one's result.  The program hands its own last tokens
        back as its LAST result, [B] int32 whatever ``eff`` is and whatever
        rides home beside the tokens: one signature feeds every next one."""
        model = self.model
        pool = self.adapters.pool if self.adapters is not None else None

        # token_mask and the lora tail are ALWAYS in the signature:
        # constrained rows upload their automaton mask rows, the rest
        # ride the cached all-True mask (an exact sampler no-op), and
        # adapter swaps change only the gathered rows — so turning
        # either feature on after warmup() never recompiles
        kinds = self._cache_kinds

        def llm_decode(params, buffers, caches, page_tbl, tokens, feed,
                       from_host, pos, do_sample, temperature, top_k, top_p,
                       token_mask, base_key, offset, lora_tree, lora_rows,
                       *accs):
            with jax.named_scope("sampler"):
                keys = jax.random.split(
                    jax.random.fold_in(base_key, offset), eff)
            with jax.named_scope("token_feed"):
                tokens = jnp.where(from_host, tokens[:, 0], feed)[:, None]
            # the tick masks the table rows of idle and mid-prefill
            # slots to the trash page: such a row is computed like the
            # others but advances no recurrent state and counts nowhere
            rows = None if kinds is None else _SlotRows(
                None, None, (page_tbl[:, 0] != 0).astype(jnp.int32))
            restore = model.bind_functional_state(params, buffers)
            try:
                with tape.no_grad(), _lora_ctx(pool, lora_tree, lora_rows):
                    def tick(carry, key):
                        caches, tok, p = carry[:3]
                        logits, new_caches = model.generate_step(
                            Tensor(tok), caches=_to_model_caches(
                                kinds, caches, p, page_tbl, rows))
                        raw, aux = _from_model_caches(kinds, new_caches)
                        nxt = _select_rows(
                            logits._value[:, -1], key, do_sample, temperature,
                            top_k, top_p, token_mask=token_mask)
                        acc = tuple(a.at[0].add(x)
                                    for a, x in zip(carry[3:], aux))
                        return (raw, nxt[:, None], p + 1) + acc, nxt

                    carry, toks = jax.lax.scan(
                        tick, (caches, tokens, pos) + accs, keys)
            finally:
                restore()
            last = toks[-1].astype(jnp.int32)
            if accs:
                # the layers' counts ride home with the tokens: one array
                return (jnp.concatenate(
                    [toks.T.reshape(-1)] + [a.reshape(-1) for a in carry[3:]]),
                    carry[0]) + tuple(carry[3:]) + (last,)
            return toks.T, carry[0], last  # [B, chunk]

        return jax.jit(llm_decode, donate_argnums=(2,) + tuple(
            range(17, 17 + len(self._accs()))))

    def _verify_fn(self):
        """ONE compiled speculative verify: score K drafts + one bonus
        position for every slot (S = K+1 through the same cache scatter /
        attention paths decode uses — on tile-aligned shapes that is
        the ragged Pallas kernel walking the page tables, not a gathered
        dense pass) and run the accept/rollback decision on device
        (ops/sampling.spec_accept) — only the [B, K+1] token ladder and
        the [B] accept counts are copied to the host.  Randomness arrives
        as ``(base_key, offset)`` like _decode_fn's."""
        model = self.model
        pool = self.adapters.pool if self.adapters is not None else None

        def llm_spec_verify(params, buffers, caches, page_tbl, tokens, drafts,
                            pos, do_sample, temperature, top_k, top_p,
                            base_key, offset, lora_tree, lora_rows):
            key = jax.random.fold_in(base_key, offset)
            restore = model.bind_functional_state(params, buffers)
            try:
                with tape.no_grad(), _lora_ctx(pool, lora_tree, lora_rows):
                    ids_in = jnp.concatenate([tokens, drafts], axis=1)
                    logits, new_caches = model.verify_step(
                        Tensor(ids_in), caches=_to_model_caches(
                            self._cache_kinds, caches, pos, page_tbl))
                    raw, _ = _from_model_caches(self._cache_kinds, new_caches)
                    out, n_acc = _spec_accept(
                        logits._value, drafts, key, do_sample, temperature,
                        top_k, top_p)
            finally:
                restore()
            return out, n_acc, raw

        return jax.jit(llm_spec_verify, donate_argnums=(2,))

    def _get_verify(self):
        if self._verify_jit is None:
            _profiling.record_compile("verify")
            self._verify_jit = self._verify_fn()
        return self._verify_jit

    def step(self):
        """One engine tick: admit pending prompts (one chunk), dispatch the
        next program for every active slot — with the chunk aboard where the
        model takes both kinds of row through one weight pass (llm_mixed),
        else behind the chunk's own program — then book the tokens of the
        program dispatched a tick earlier (_decode_tick); a first token is
        read at the end of the tick that dispatched llm_prefill_chunk, or
        with the booking of the llm_mixed that carried the final chunk, a
        tick after its dispatch (_first_token), and its slot joins the next
        program dispatched.  After a hand-driven step() a decode result is
        as a rule still in flight;
        run_until_complete() and drain() leave none.  Serialized by the
        engine lock: the
        background pump and caller-thread pumping (run_until_complete) must
        not race on the DONATED cache buffers or the slot state."""
        with self._lock:
            if not _obs.enabled():
                out = self._step_locked()
                self._first_tick_done = True
                return out
            # goodput ledger: a draining tick runs under a queue_drain
            # section — the compute carves (decode/prefill/verify) debit
            # it, so queue_drain holds only the drain's overhead slice
            drain_sec = (self._goodput.section("queue_drain")
                         if self._draining else _goodput.NULL)
            pc = self._phases
            with drain_sec:
                with _span("llm_decode_tick", _M_TICK_SECONDS) as sp:
                    pc.begin()
                    try:
                        emitted = self._step_locked()
                    finally:
                        pc.end()  # no phase stays open across ticks
                dec = 0.0
                for phase, child in _TICK_PHASE_SERIES.items():
                    d = pc.seconds[phase] - self._phase_pub[phase]
                    if d > 0.0:
                        child.inc(d)
                        self._phase_pub[phase] = pc.seconds[phase]
                        if phase in _DECODE_PHASES:
                            dec += d
                # goodput ledger: the productive decode seconds ARE the
                # tick's three decode_* phases (arg staging + compiled call
                # + the wait for a result), in whichever order it ran them;
                # bookkeeping stays in the idle/queue_drain residual
                self._goodput.carve("decode", dec)
            self._first_tick_done = True
            if emitted:
                self._goodput.count_tokens("useful", emitted)
            if sp.duration:
                _slo.track("llm_tick", sp.duration)
            if emitted and sp.duration:
                _M_DECODE_TOKENS.inc(emitted)
                _M_DECODE_TPS.set(emitted / sp.duration)
            return emitted

    def _step_locked(self):
        pc = self._phases
        pc.switch("expire")
        self._expire_queued()
        self._expire_slots()
        pc.switch("admit")
        self._admit_paged()
        pc.switch("bookkeep")
        self._update_page_gauges()
        _M_QUEUE_DEPTH.set(self._pending.qsize())
        emitted = self._decode_tick()
        if self._chunk_out is not None:
            out, self._chunk_out = self._chunk_out, None
            self._first_token(*out)
            # only now drop the in-flight marker: drain()'s lock-free
            # _drained() must never observe _prefilling cleared while the
            # slot is not yet active, or it declares the engine empty with
            # this request still about to decode
            self._prefilling = None
        return emitted

    def _count_drain(self, why):
        self._pipeline["drained"][why] += 1
        _PIPELINE_SERIES[why].inc()

    def _read_decode(self, why):
        """Read the decode result in flight with no later program queued
        behind it, and book it; ``why`` names what kept the pump from
        running ahead (_DRAIN_REASONS).  Returns the tokens emitted."""
        fl, self._inflight = self._inflight, None
        self._count_drain(why)
        return self._book(fl)

    def _decode_tick(self):
        """The decode half of a tick.  The pump keeps ONE decode program in
        flight: it dispatches this tick's program BEFORE it reads the
        previous one's result, whose tokens reach this program on the device
        (llm_decode's ``feed``), and books that result — emit, stamp,
        finish, publish the counts — while the device runs this one.  A
        chunk _prefill_tick left staged rides the SAME program (llm_mixed in
        llm_decode's place: one pass over the weights for the chunk's rows
        and the decode rows; with no row decoding, the chunk alone); a final
        chunk hands its slot over here, with its first token in flight like
        any row's next one, selected where the program is booked (_book):
        the slot joins the program dispatched after that.  The
        host does not need the tokens to dispatch: positions advance by one
        a token, pages grow by count, a row that reaches max_new_tokens or
        the end of its cache with the token in flight is left out (and
        finished when that token is read).  Only an EOS is learnt one
        program late: the row's surplus token is dropped and counted.

        Where the next program DOES need what the host has not read, the
        pump reads first and the tick is synchronous (dispatch, then read)
        — decided by what it can observe, tick by tick: a constrained row
        (its mask follows the token), a speculating engine (the drafter
        reads the tokens), a slot to preempt (its prompt regrows from all
        its tokens); with no row to run it reads what is in flight and goes
        idle.  Returns the tokens booked this tick."""
        pc = self._phases
        reqs = self.slot_req
        emitted = 0
        chunk, self._staged = self._staged, None
        # a constrained row's automaton state advances per TOKEN, and the
        # uploaded mask is constant across a chunk — so ticks with any
        # constrained row decode one token at a time, each read at once
        constrained = any(r is not None and r.cursor is not None for r in reqs)
        sync = "constrained" if constrained else "spec" if self.spec_k \
            else None
        if sync and self._inflight is not None:
            emitted += self._read_decode(sync)
        _M_ACTIVE_SLOTS.set(sum(r is not None for r in reqs))
        # rows the program carries: not those that end, by count, with the
        # token in flight — they compute nothing more
        rows = [i for i in range(self.n_slots) if self._runs_on(i)]
        if not rows and chunk is None:
            if self._inflight is not None:
                emitted += self._read_decode("idle")
            return emitted
        # effective chunk: stay inside the cache (slots AT capacity are
        # left out above, so headroom >= 1)
        headroom = self.L - 1 - int(
            (self.slot_pos[rows] + self._ahead[rows]).max(initial=0))
        if self.spec_k and headroom >= self.spec_k:
            # speculative tick: verify writes rows pos .. pos+K, so it
            # needs K rows of headroom; the last strides before capacity
            # fall back to plain one-token decode below
            return emitted + self._spec_tick(rows)
        eff = 1 if constrained else max(1, min(self.decode_chunk, headroom))
        pc.switch("decode_stage")
        # grow page tables to cover this tick's writes; slots the pool
        # cannot cover any longer are preempted (shed, not wedged)
        grown = self._ensure_decode_pages(rows, eff)
        if grown is None:
            emitted += self._read_decode("preempt")
            pc.switch("decode_stage")
            grown = self._ensure_decode_pages(
                [i for i in rows if self._runs_on(i)], eff)
        rows = grown
        self._update_page_gauges()
        if not rows and chunk is None:
            pc.switch("bookkeep")
            return emitted
        # host arrays straight into the compiled call, and (key, offset)
        # for the keys it derives itself: no eager device call here (see
        # _sampling_knobs).  Copies: bookkeeping writes these in place.
        # A row with tokens in flight takes the newest of them from the
        # device, at the position behind them
        tokens = self.last_token.reshape(-1, 1).copy()
        from_host = self._ahead == 0
        pos = self.slot_pos + self._ahead
        do_s, temp, topk, topp = self._sampling_knobs(rows)
        if rows:  # a chunk alone selects no row's token
            self._count_sampler_tick(do_s, topk, topp)
        # read each tick, not held: paddle.seed() on a live engine governs
        rng = _fr.default_generator().fork()
        if constrained:
            # per-row [V] masks from each constrained row's automaton
            # state; unconstrained rows stay all-True (exact no-op)
            token_mask = np.ones((self.n_slots, self._vocab), bool)
            for i in rows:
                if reqs[i].cursor is not None:
                    token_mask[i] = reqs[i].cursor.mask()
        else:
            token_mask = self._mask_all_true
        args = (*self._cache_args(rows), tokens, self._feed, from_host, pos,
                do_s, temp, topk, topp, token_mask, *rng)
        moe = None
        if self._moe_acc is not None:
            # what this program's counts will cover: every dispatch since
            # the decode program before it, chunks included
            self._moe_dispatched("decode", len(rows), eff)
            moe, self._moe_pending = self._moe_pending, np.zeros_like(
                self._moe_pending)
        first = None
        if chunk is None:
            pc.switch("decode_dispatch")
            out = self._took_decode(self._get_decode(eff)(
                *args, *self._lora_args(
                    [r.adapter_page if r is not None else 0 for r in reqs]),
                *self._accs()))
        else:
            both = self._dispatch_chunk(chunk, args + chunk.args)
            if both is None:
                return emitted  # its request failed: the rows run next tick
            out, logits = both
            if rows:
                self._pipeline["mixed"] += 1
                _PIPELINE_SERIES["mixed"].inc()
            if chunk.final:
                first = (chunk.req, chunk.slot, logits)
        prev, self._inflight = self._inflight, _InFlight(
            out, eff, [(i, reqs[i]) for i in rows], moe, first)
        self._ahead[rows] += eff
        if first is not None:
            # the slot is the request's from here on, though no program
            # carries it until its first token is booked; only now does the
            # prefill marker go (drain()'s lock-free _drained() must find
            # the request somewhere at every instant)
            req, slot, _ = first
            req.slot = slot
            reqs[slot] = req
            self.slot_pos[slot] = req.prompt.size
            self._prefilling = None
        if prev is not None:
            # the device is busy with the program just dispatched
            self._pipeline["overlapped"] += 1
            _PIPELINE_SERIES["overlapped"].inc()
            emitted += self._book(prev)
        if sync:
            emitted += self._read_decode(sync)
        elif prev is None:
            pc.switch("bookkeep")
        return emitted

    def _awaits_first(self, slot):
        """Whether the slot's request still waits for its first token: the
        program in flight carried its final chunk (llm_mixed), and no
        program carries its row until that one is booked."""
        fl = self._inflight
        return fl is not None and fl.first is not None and fl.first[1] == slot

    def _runs_on(self, slot):
        """Whether the next decode program carries the slot: it holds a
        request that has its first token and does not end, by count, with
        the tokens it has in flight (at max_new_tokens, or at the end of
        its cache)."""
        req, ahead = self.slot_req[slot], int(self._ahead[slot])
        return (req is not None and not self._awaits_first(slot)
                and len(req.tokens) + ahead < req.max_new_tokens
                and int(self.slot_pos[slot]) + ahead < self.L - 1)

    def _book(self, fl):
        """Wait for a dispatched decode program and book its tokens: emit,
        stamp, advance, finish, publish the layers' counts; then the first
        token of the request whose final chunk it carried (_first_token:
        that slot joins the next program dispatched).  A row whose
        slot no longer holds the request it was dispatched for — it met its
        EOS in the program before, or expired — has its tokens dropped and
        counted as surplus."""
        pc = self._phases
        pc.switch("decode_sync")
        nxt = np.asarray(fl.out).astype(np.int32)  # [B, eff]
        B, eff = self.n_slots, fl.eff
        if nxt.ndim == 1:
            # the layers' counts came in the same array, in _accs() order
            counts = nxt[B * eff:]
            nxt = nxt[:B * eff].reshape(B, eff)
        # every token of this program carries this stamp: the instant the
        # host had them.  It is also the boundary into bookkeeping
        now_pc = pc.switch("bookkeep") \
            or time.perf_counter()  # the clock is off: read it
        if fl.moe is not None:
            self._publish_moe(counts[:self._moe_seen.size], fl.moe)
        if self._sparse_acc is not None or self._latent_acc is not None:
            self._publish_counts(counts)
        emitted = 0
        for i, req in fl.rows:
            if self.slot_req[i] is not req:
                self._pipeline["surplus_tokens"] += eff
                continue
            self._ahead[i] -= eff
            for j in range(eff):
                tok = int(nxt[i, j])
                self._emit_token(req, tok, now_pc)
                if req.cursor is not None:
                    # host automaton tracks the device-selected token; the
                    # NEXT tick's mask upload reads the advanced state
                    req.cursor.advance(tok)
                    _constrain.count_masked_token()
                self.last_token[i] = tok
                self.slot_pos[i] += 1
                emitted += 1
                if (tok == self.eos
                        or len(req.tokens) >= req.max_new_tokens
                        or self.slot_pos[i] >= self.L - 1):
                    # the rest of the chunk is surplus; so is what a later
                    # program already dispatched computes for this row
                    self._finish(i)
                    break
            else:
                if len(req.token_ts) - req.dec_i0 >= _DECODE_SPAN_TICKS:
                    self._flush_decode_span(req)  # bound spans per episode
        if fl.first is not None and self.slot_req[fl.first[1]] is fl.first[0]:
            # the final chunk this program carried: the logits came with the
            # tokens.  (Its request gone from the slot — expired, stopped —
            # they are dropped like a row's token.)
            self._first_token(*fl.first)
        return emitted

    def _spec_tick(self, active):
        """One speculative tick: host-draft K tokens per active slot, ONE
        compiled verify pass over S = K+1 positions for the whole pool,
        emit each slot's accepted prefix + correction token, then roll
        back — the slot position simply stops at the accept point, and
        pages holding only rejected rows return to the pool."""
        K = self.spec_k
        pc = self._phases
        pc.switch("spec_stage")
        # the verify writes rows pos .. pos+K: grow/COW the page tables
        # for all K+1 rows up front; a slot the pool cannot cover
        # mid-verify preempts recompute-style, same as decode
        active = self._ensure_decode_pages(active, K + 1, origin="verify")
        self._update_page_gauges()
        if not active:
            pc.switch("bookkeep")
            return 0
        t0 = pc.switch("spec_draft")
        drafts = np.zeros((self.n_slots, K), np.int32)
        for i in active:
            req = self.slot_req[i]
            ctx = np.concatenate(
                [req.prompt, np.asarray(req.tokens[req.regrown:], np.int32)])
            drafts[i] = self._drafter.propose(ctx, K)
        draft_s = pc.switch("spec_stage") - t0
        reqs = self.slot_req
        do_s, temp, topk, topp = self._sampling_knobs()
        self._count_sampler_tick(do_s, topk, topp)
        # host arrays and (key, offset), as the decode tick stages them
        args = (*self._cache_args(),
                self.last_token.reshape(-1, 1).copy(), drafts,
                self.slot_pos.copy(), do_s, temp, topk, topp,
                *_fr.default_generator().fork(), *self._lora_args(
                    [r.adapter_page if r is not None else 0 for r in reqs]))
        jit = self._get_verify()
        # the verify window (the span, the goodput carve, the request's
        # verify_s) is spec_dispatch + spec_sync, boundary for boundary
        with _span("llm_spec_verify", _M_SPEC_VERIFY_S) as sp:
            t1 = pc.switch("spec_dispatch")
            out_dev, n_dev, self.caches = jit(*args)
            pc.switch("spec_sync")
            out = np.asarray(out_dev).astype(np.int32)
            n_acc = np.asarray(n_dev).astype(np.int32)
            t_end = pc.switch("spec_accept")
        if sp.duration:
            _slo.track("llm_verify", sp.duration)
        verify_s = t_end - t1
        # every token of this tick carries this stamp
        now_pc = t_end or time.perf_counter()  # the clock is off: read it
        emitted = 0
        drafted_tick = 0
        accepted_tick = 0
        rb_pages = 0
        for i in list(active):
            req = self.slot_req[i]
            if req is None:
                continue
            if _obs.enabled():
                req.spec_drafted += K
                req.spec_accepted += int(n_acc[i])
                req.spec_draft_s += draft_s
                req.spec_verify_s += verify_s
            drafted_tick += K
            accepted_tick += int(n_acc[i])
            # row i emits out[i, :n_acc[i]+1]: the accepted drafts plus
            # one correction/bonus token (so every verify makes progress)
            for j in range(int(n_acc[i]) + 1):
                tok = int(out[i, j])
                self._emit_token(req, tok, now_pc)
                self.last_token[i] = tok
                self.slot_pos[i] += 1
                emitted += 1
                done = (tok == self.eos
                        or len(req.tokens) >= req.max_new_tokens
                        or self.slot_pos[i] >= self.L - 1)
                if done:
                    self._finish(i)
                    break
            if self.slot_req[i] is not None:
                rb_pages += self._trim_rollback_pages(i)
        rolled = drafted_tick - accepted_tick
        # goodput ledger: split the draft+verify compute by acceptance —
        # the rejected-draft share of the window bought nothing, so it is
        # spec_rollback_waste, not verify; rolled tokens join the token
        # ledger's waste class
        spec_s = draft_s + verify_s
        if drafted_tick:
            waste_s = spec_s * (rolled / drafted_tick)
            self._goodput.carve("verify", spec_s - waste_s)
            self._goodput.carve("spec_rollback_waste", waste_s)
        else:
            self._goodput.carve("verify", spec_s)
        if rolled:
            self._goodput.count_tokens("spec_rolled_back", rolled)
        self._spec_drafted += drafted_tick
        self._spec_accepted += accepted_tick
        self._spec_rolled_back += rolled
        self._spec_rb_pages += rb_pages
        self._spec_verifies += 1
        _M_SPEC_DRAFTED.inc(drafted_tick)
        _M_SPEC_ACCEPTED.inc(accepted_tick)
        if rolled:
            _M_SPEC_ROLLED_BACK.inc(rolled)
        if rb_pages:
            _M_SPEC_RB_PAGES.inc(rb_pages)
        if self._spec_drafted:
            _M_SPEC_ACCEPT_RATIO.set(
                self._spec_accepted / self._spec_drafted)
        self._update_page_gauges()
        for i in active:
            req = self.slot_req[i]
            if req is not None \
                    and len(req.token_ts) - req.dec_i0 >= _DECODE_SPAN_TICKS:
                self._flush_decode_span(req)
        return emitted

    def _trim_rollback_pages(self, slot):
        """Free pages holding ONLY rejected verify rows: valid rows are
        0 .. slot_pos-1, so every page past the one holding row
        slot_pos-1 was grown for drafts that rolled back.  Those pages
        are exclusively owned (freshly allocated or COW-forked by
        _ensure_decode_pages), so the decref hands them straight back to
        the pool for other slots THIS tick instead of next."""
        keep = (int(self.slot_pos[slot]) - 1) // self.ps + 1
        pages = self._slot_pages[slot]
        trimmed = 0
        while len(pages) > keep:
            page = pages.pop()
            self._pt_host[slot, len(pages)] = 0
            self._decref(page)
            trimmed += 1
        return trimmed

    def _expire_queued(self):
        """Fail and evict expired (or caller-cancelled) requests anywhere in
        the admission queue — with every slot busy, _admit never pops them,
        yet they must not pin the bounded queue's capacity.

        Works in place under the Queue's own mutex: submit()'s put_nowait
        is not serialized by the engine lock, so drain-and-requeue would
        race it.  (This bypasses unfinished_tasks, so _pending.join() must
        never be used on this queue — the engine doesn't.)"""
        now = self._clock()
        expired = []
        evicted = []
        with self._pending.mutex:
            keep = []
            for req in self._pending.queue:
                if req.future.done():  # cancelled/failed: just drop it
                    evicted.append(req)
                elif req.deadline is not None and now > req.deadline:
                    expired.append(req)
                else:
                    keep.append(req)
            if expired or evicted:
                self._pending.queue.clear()
                self._pending.queue.extend(keep)
                self._pending.not_full.notify_all()
        for req in evicted:
            self._end_trace(req, "cancelled")
        for req in expired:
            _M_EXPIRED.labels(where="queued").inc()
            _flight.record_event("deadline_expiry", where="queued",
                                 **_trace_kv(req))
            _fail_future(req.future, DeadlineExceededError(
                "request deadline expired while queued for admission"))
            self._end_trace(req, "expired", where="queued")

    def _expire_slots(self):
        """Fail and free any in-flight slot whose deadline has passed —
        graceful degradation: a slow request never wedges its slot."""
        for i, req in enumerate(self.slot_req):
            if req is not None and req.deadline is not None \
                    and self._clock() > req.deadline:
                self._vacate(i)  # a token in flight for the row is dropped
                _M_EXPIRED.labels(where="inflight").inc()
                _flight.record_event("deadline_expiry", where="inflight",
                                     slot=int(i), tokens=len(req.tokens),
                                     **_trace_kv(req))
                _fail_future(req.future, DeadlineExceededError(
                    f"request deadline exceeded after "
                    f"{len(req.tokens)} generated tokens"))
                self._end_trace(req, "expired", where="inflight")

    def _finish(self, slot):
        req = self._vacate(slot)
        if req is not None:
            _M_COMPLETED.inc()
            if req.submit_ts is not None:
                e2e = max(0.0, self._clock() - req.submit_ts)
                _M_E2E.observe(e2e, exemplar=req.trace.trace_id or None)
                if _slo.track("llm_e2e", e2e):
                    req.trace.mark_slo("llm_e2e")
            self._end_trace(req, "ok")
            _complete_future(req.future, list(req.tokens))
