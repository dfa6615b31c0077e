"""Continuous-batching serving engine for the (sparse) LM stack.

The engine holds a static-shape batch of ``max_slots`` sequences — shapes
never change, so XLA compiles the decode step exactly once.  Between decode
steps it *admits* queued requests into free slots (prefill writes the
request's K/V straight into its slot via ``prefill_into_slot``) and every
decode step advances all occupied slots at their own positions (the
per-slot position vector threaded through ``decode_step`` /
``decode_attention``).  Finished slots are freed immediately and the next
admission overwrites them — the paper's sparse-serving scenario (Fig 11)
run as a service rather than a one-shot batch.

Decoding is *chunked*: when every active request is greedy, the engine
runs ``decode_chunk`` steps in one jitted ``lax.scan`` with on-device
argmax sampling and fetches the whole token block in a single host sync
(the serving analogue of the trainer's ``make_multi_step``), instead of
blocking on the device once per token.  Requests with non-greedy sampling
fall back to the per-token loop so their host-side RNG streams stay
reproducible and batch-independent.

The sparse path is the point: ``sparsify_for_serving`` converts FFN
weights to :class:`GroupedNMTensor` through the ordinary
:class:`SparsityBuilder`, and because layouts are pytrees the engine's
jitted prefill/decode accept dense and n:m:g params interchangeably.
``compare_dense_sparse`` serves the same trace under both and reports the
numbers side by side.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.builder import SparsityBuilder
from repro.core.layouts import GroupedNMTensor
from repro.core.sparsifiers import GroupedNMSparsifier
from repro.models import commit_append_buffer, decode_step, \
    decode_step_buffered, init_append_buffer, init_cache, prefill
from repro.models.common import ModelConfig
from repro.obs import trace as obs
from repro.obs.registry import REGISTRY, MirroredCounters
from repro.serve.cache import PagedKVCache, PromptTooLongError, \
    SlotKVCache, paged_commit, paged_view
from repro.serve.errors import EngineOverloadError, InjectedFaultError, \
    ServeError
from repro.serve.faults import FaultInjector
from repro.serve.metrics import ServeMetrics, summarize
from repro.serve.queue import Request, RequestOutput, RequestQueue, \
    sample_token
from repro.serve.slo import LatencyModel, SLOConfig, SLOController, \
    build_tiers
from repro.serve.tracecount import note_trace

__all__ = ["ServeEngine", "sparsify_for_serving", "compare_dense_sparse",
           "warmup_engine", "serve_programs", "window_masked_rows"]


#: bound on the per-config jitted-closure caches below.  Each entry pins a
#: jitted callable whose own executable cache grows per traced
#: (param-structure, shape) — in a long-running engine serving many model
#: configs that accumulates without limit, so unlike the read-only pattern
#: tables in ``core/layouts.py`` (tiny numpy constants, safe to keep
#: forever) these caches are LRU-bounded; eviction only costs a recompile
#: if a config comes back.
_JIT_CACHE_SIZE = 16

#: default slot-batch size — single source for ``ServeEngine.__init__``
#: and the warmup tuner's decode-width fallback, which must agree on the
#: width a default-constructed engine actually decodes at
DEFAULT_MAX_SLOTS = 8


def _decode_fn(cfg: ModelConfig):
    """The raw (unjitted) per-token decode callable the engine compiles.
    Split out of :func:`_jit_decode` so ``repro.check`` can trace the
    *identical* program the runtime jits."""

    def step(p, tok, cache, pos):
        note_trace("decode")  # trace-time only: counts compilations
        return decode_step(p, cfg, tok, cache, pos)

    return step


def _greedy_chunk(p, cfg: ModelConfig, tok, history, pos, n_steps: int):
    """``n_steps`` greedy decode steps over a read-only ``history`` (the
    slot cache, or the paged view) under one ``lax.scan``.  The scan
    carries only the token, the chunk's append buffer and the position;
    the history stays outside the carry.  Returns the [n_steps, B] tokens
    and the buffer, for the caller to commit at ``pos``."""

    def body(carry, _):
        tok, buf, pv = carry
        logits, buf = decode_step_buffered(p, cfg, tok, history, buf, pos,
                                           pv)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)   # [B] on device
        return (nxt[:, None], buf, pv + 1), nxt

    with jax.named_scope("decode.chunk"):
        buf = init_append_buffer(cfg, history, n_steps)
        (_, buf, _), toks = jax.lax.scan(
            body, (tok, buf, pos), None, length=n_steps
        )
    return toks, buf


def _decode_chunk_fn(cfg: ModelConfig, n_steps: int):
    """The raw chunked decode loop body (see :func:`_jit_decode_chunk`),
    split out for the same reason as :func:`_decode_fn`."""

    def chunk(p, tok, cache, pos):
        note_trace("decode_chunk")  # trace-time only: counts compilations
        toks, buf = _greedy_chunk(p, cfg, tok, cache, pos, n_steps)
        return toks, commit_append_buffer(cfg, cache, buf, pos)

    return chunk


@functools.lru_cache(maxsize=_JIT_CACHE_SIZE)
def _jit_decode(cfg: ModelConfig):
    """One jitted decode step per config (ModelConfig is frozen/hashable),
    shared across engine instances so a dense-vs-sparse comparison only
    compiles each (config, param-structure) once.  The cache operand is
    donated — the hot path updates the KV pool in place every token
    instead of copying it."""
    return jax.jit(_decode_fn(cfg), donate_argnums=(2,))


@functools.lru_cache(maxsize=2 * _JIT_CACHE_SIZE)  # keyed (cfg, n_steps)
def _jit_decode_chunk(cfg: ModelConfig, n_steps: int):
    """Jitted multi-token inner decode loop (the serving analogue of
    ``launch/train.py:make_multi_step``): ``n_steps`` decode steps under one
    ``lax.scan`` with on-device greedy sampling, so the host syncs once per
    chunk instead of once per token.  Returns the [n_steps, max_slots]
    token matrix (the single chunked host fetch) plus the updated cache."""
    return jax.jit(_decode_chunk_fn(cfg, n_steps), donate_argnums=(2,))


def serve_programs(params, cfg: ModelConfig, *, max_slots: int = 4,
                   max_seq_len: int = 64, decode_chunk: int = 4,
                   prompt_len: int = 8) -> dict:
    """The engine's compiled surface as ``{name: (fn, example_args)}`` —
    the exact callables :func:`_jit_decode` / :func:`_jit_decode_chunk` /
    the admission prefill jit, with example arguments shaped the way a
    running engine shapes them.  ``repro.check`` traces these, so a
    diagnostic on a ``serve:*`` program is a diagnostic on the real
    serving fast path, not on a checker-only approximation."""
    tok = jnp.zeros((max_slots, 1), jnp.int32)
    cache = init_cache(cfg, max_slots, max_seq_len)
    pos = jnp.full((max_slots,), prompt_len, jnp.int32)
    progs = {
        "decode": (_decode_fn(cfg), (params, tok, cache, pos)),
        "prefill": (
            lambda p, toks: prefill(p, cfg, toks, cache_len=max_seq_len),
            (params, jnp.zeros((1, prompt_len), jnp.int32)),
        ),
    }
    if decode_chunk > 1:
        progs["decode_chunk"] = (
            _decode_chunk_fn(cfg, decode_chunk), (params, tok, cache, pos),
        )
    return progs


@functools.lru_cache(maxsize=_JIT_CACHE_SIZE)
def _jit_paged_decode(cfg: ModelConfig, page_size: int, num_pages: int):
    """Paged analogue of :func:`_jit_decode`: gather the slot-major
    logical cache out of the page pool through the table, run one decode
    step over it read-only with a one-row append buffer, and commit that
    row per slot to its physical page.  The pool is donated — the commit
    updates it in place."""

    def step(p, tok, pool, table, pos):
        note_trace("paged_decode")  # trace-time only: counts compilations
        view = paged_view(cfg, pool, table, page_size)
        logits, buf = decode_step_buffered(
            p, cfg, tok, view, init_append_buffer(cfg, view, 1), pos, pos)
        pool = paged_commit(cfg, pool, buf, table, pos, page_size,
                            num_pages)
        return logits, pool

    return jax.jit(step, donate_argnums=(2,))


@functools.lru_cache(maxsize=2 * _JIT_CACHE_SIZE)
def _jit_paged_decode_chunk(cfg: ModelConfig, page_size: int,
                            num_pages: int, n_steps: int):
    """Paged analogue of :func:`_jit_decode_chunk`: one gather, ``n_steps``
    decode steps over the read-only slot-major view with the new rows in
    an append buffer (the exact loop the slot cache runs, so greedy tokens
    match it bitwise), then one commit of the buffer's ``n_steps`` rows
    per slot.  The engine guarantees (via ``ensure_writable_range``) that
    every mapped page in the write range is private before this runs;
    unmapped/overshoot destinations resolve to the sentinel page and are
    dropped."""

    def chunk(p, tok, pool, table, pos):
        note_trace("paged_decode_chunk")  # trace-time: counts compilations
        view = paged_view(cfg, pool, table, page_size)
        toks, buf = _greedy_chunk(p, cfg, tok, view, pos, n_steps)
        pool = paged_commit(cfg, pool, buf, table, pos, page_size,
                            num_pages)
        return toks, pool

    return jax.jit(chunk, donate_argnums=(2,))


def sparsify_for_serving(params, n: int = 1, m: int = 4, g: int = 16,
                         gr: int = 64, *, attn: bool = False):
    """Convert FFN weights to the n:m:g inference layout (paper §5.3:
    'our sparse-dense GEMM kernel during inference').

    ``gr`` shares each chunk permutation across ``gr`` consecutive output
    fibers (the row-sharing format adaptation).  For serving it defaults
    to 64: the decode GEMV and prefill SpMM kernels amortize their B-row
    gathers across the shared rows and contract them as one dense tile,
    which is what makes the sparse path *faster* than dense rather than
    gather-bound (gr=1, the paper's per-fiber CPU format, keeps maximal
    energy but pays one gather per stored value per call).

    ``attn=True`` additionally sparsifies the attention projections
    (wq/wk/wv/wo).  q/k/v then share one format over the same contraction
    axis, so the decode step routes them through the fused QKV megakernel
    (one launch per step instead of three — ``kernels/nmg_fused.py``);
    the packed gated-MLP ``wi`` likewise takes the fused projection+gate
    launch.

    Only the weights named above change layout: every other leaf, the
    biases of ``proj_bias`` / ``qkv_bias`` and the norms among them, stays
    dense, and the model adds each bias to its n:m:g product."""
    sb = SparsityBuilder()
    sp = GroupedNMSparsifier(n, m, g, gr, sparse_dim=0)  # [K, N] weights
    sb.set_weight("*mlp.wi", sp, GroupedNMTensor)
    sb.set_weight("*mlp.wo", sp, GroupedNMTensor)
    if attn:
        for name in ("*attn.wq", "*attn.wk", "*attn.wv", "*attn.wo"):
            sb.set_weight(name, sp, GroupedNMTensor)
    return sb.sparsify_params(params)


def window_masked_rows(cfg: ModelConfig, first: int, n: int) -> int:
    """Cached rows the sliding window hides from the queries at positions
    ``first .. first + n - 1``, summed over the local layers: a query at
    position p sees keys ``p - window < j <= p``, so ``max(0, p + 1 -
    window)`` of its causal rows are hidden in each local layer."""
    W = cfg.local_window
    layers = {"local": cfg.n_layers,
              "alt_local_global": cfg.n_layers // 2}.get(cfg.layer_pattern,
                                                          0)
    if not W or not layers:
        return 0

    def upto(p):  # hidden rows of the queries at positions 0 .. p - 1
        k = max(0, p - W)
        return k * (k + 1) // 2

    return layers * (upto(first + n) - upto(first))


@dataclasses.dataclass
class _SlotState:
    """Host-side bookkeeping for one occupied slot."""

    req: Request
    tokens: list
    token_times: list
    admitted_time: float
    rng: np.random.Generator
    max_new: int  # request budget clamped to the slot's cache capacity


class ServeEngine:
    """Slot-based continuous-batching engine.

    Parameters
    ----------
    params : dense or sparse (layout-bearing) model params pytree
    cfg : model config
    max_slots : batch size of the static decode step
    max_seq_len : per-slot KV capacity (prompt + generation)
    reset_freed_slots : zero a slot's cache when its request finishes.
        Admission overwrites whatever a slot holds and decode masks each
        slot to its own prefix, so this is off by default; tests use it to
        prove slot isolation.
    decode_chunk : decode steps per jit call between admissions.  When every
        active request decodes greedily, the engine runs ``decode_chunk``
        steps device-resident (``lax.scan`` with on-device sampling) and
        fetches the whole token block in one host sync; tokens past a stop
        condition are discarded host-side.  1 restores the per-token
        reference loop; any non-greedy active request also falls back to it
        (host-side RNG sampling keeps per-request streams batch-independent).
    clock : timestamp source (injectable for deterministic tests)
    paged : back the KV cache with :class:`PagedKVCache` instead of
        :class:`SlotKVCache`.  Decode runs the same decode core as the
        slot cache over a gathered slot-major view of the page pool (read
        only; the new rows go into an append buffer), so outputs match the
        slot cache token-for-token; what changes is capacity — with
        ``num_pages`` oversubscribed relative to
        ``max_slots * max_seq_len / page_size``, short prompts and shared
        prefixes let many more concurrent requests fit the same memory.
        Admission that cannot get pages *defers* (the request returns to
        the queue head; live slots are never corrupted) and a decode step
        that cannot get pages preempts the youngest slot, whose request is
        re-served from scratch (identical output: greedy decoding, and
        non-greedy streams restart their seeded RNG).
    page_size, num_pages, prefix_sharing : forwarded to
        :class:`PagedKVCache` when ``paged``.
    slo : :class:`~repro.serve.slo.SLOConfig` enabling the SLO control
        loop: a hysteresis state machine over the degradation ladder
        (defer admissions / shrink decode chunk -> sparser weight tier ->
        shed lowest-priority queued work), driven by a decode-cadence
        watchdog and a table-seeded latency model.
    tiers : sparsity-tier specs (densest first — strings like ``"dense"``,
        ``"2:4"``, ``"1:4:8-gr64"`` or :class:`~repro.serve.slo.TierSpec`),
        pre-converted once here so a controller tier switch is a pytree
        pointer swap into an already-compiled decode program (call
        :meth:`warm_tiers` after construction to compile every tier
        eagerly).  ``params`` must be the *dense* weights when tiers are
        given; tier 0 is what the engine serves when healthy.
    faults : a :class:`~repro.serve.faults.FaultInjector` wrapping the
        decode/admission paths (deterministic seeded latency spikes,
        slow-decode windows, transient errors retried with capped
        exponential backoff) — the overload benchmark's chaos source.
    max_queue : bound the arrival queue; ``submit()`` past the bound
        raises :class:`~repro.serve.errors.EngineOverloadError`.
    """

    def __init__(self, params, cfg: ModelConfig, *,
                 max_slots: int = DEFAULT_MAX_SLOTS,
                 max_seq_len: int = 256, reset_freed_slots: bool = False,
                 decode_chunk: int = 8,
                 clock: Callable[[], float] = time.perf_counter,
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefix_sharing: bool = True,
                 slo: Optional[SLOConfig] = None,
                 tiers: Optional[Iterable] = None,
                 faults: Optional[FaultInjector] = None,
                 max_queue: Optional[int] = None):
        self.params = params
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.reset_freed_slots = reset_freed_slots
        self.decode_chunk = max(1, decode_chunk)
        self.paged = paged
        self.queue = RequestQueue()
        self.faults = faults
        self.max_queue = max_queue
        self.tiers = build_tiers(params, list(tiers)) if tiers else None
        self.tier_idx = 0
        if self.tiers:
            self.params = self.tiers[0].params
        self.tokens_by_tier = (
            {t.spec.name: 0 for t in self.tiers} if self.tiers else None
        )
        self.slo = slo
        if slo is not None:
            self._latency = LatencyModel(self.params, cfg,
                                         max_slots=max_slots)
            self._controller: Optional[SLOController] = SLOController(
                slo, n_tiers=len(self.tiers) if self.tiers else 1,
                max_slots=max_slots, latency=self._latency)
        else:
            self._latency = None
            self._controller = None
        #: decode-chunk sizes this engine may run (compiled at warmup):
        #: the base chunk, the controller's shrunk chunk, and 1 (the
        #: non-greedy / degraded fallback)
        self._chunk_sizes = sorted({self.decode_chunk, 1} | (
            {max(1, self.decode_chunk // max(1, slo.chunk_shrink))}
            if slo is not None else set()
        ))
        self._decode_calls = 0  # global decode-call index (fault schedule)
        if paged:
            self.kv = PagedKVCache(cfg, max_slots, max_seq_len,
                                   page_size=page_size, num_pages=num_pages,
                                   prefix_sharing=prefix_sharing)
            self._decode = _jit_paged_decode(cfg, self.kv.page_size,
                                             self.kv.num_pages)
            self._decode_chunk = (
                _jit_paged_decode_chunk(cfg, self.kv.page_size,
                                        self.kv.num_pages, self.decode_chunk)
                if self.decode_chunk > 1 else None
            )
        else:
            self.kv = SlotKVCache(cfg, max_slots, max_seq_len)
            self._decode = _jit_decode(cfg)
            self._decode_chunk = (
                _jit_decode_chunk(cfg, self.decode_chunk)
                if self.decode_chunk > 1 else None
            )
        #: scheduler counters (all zero for the slot cache except
        #: rejected/peak_active): deferred admissions, mid-stream
        #: preemptions, rejected requests, peak concurrently-active slots,
        #: plus the SLO/fault loop's shed/timeout/retry/tier-switch counts,
        #: and the decode steps run by the chunk program (append buffer
        #: over a read-only cache) against those on the one-step fallback,
        #: and ``attn_window_masked_rows``: the cached rows the sliding
        #: window hid from the queries the device ran, summed over local
        #: layers, counted from lengths alone (:func:`window_masked_rows`).
        #: Reads/writes behave exactly like the plain dict this used to
        #: be; increases additionally mirror into the telemetry registry
        #: so a benchmark's registry snapshot includes engine stats.
        self.stats = MirroredCounters(
            {"deferred_admissions": 0, "preemptions": 0,
             "rejected": 0, "peak_active": 0, "shed": 0,
             "timeout": 0, "fault_retries": 0, "tier_switches": 0,
             "decode_steps_chunked": 0, "decode_steps_single": 0,
             "attn_window_masked_rows": 0},
            REGISTRY.family("engine_stats",
                            help="engine scheduler counters"))
        # chunked decode falls back to single-step once a lone slot cannot
        # get a full chunk's pages; cleared when a request finishes (pages
        # freed) — see _ensure_decode_pages
        self._force_single = False
        self._slots: list[Optional[_SlotState]] = [None] * max_slots
        # next cache write position per slot == current valid length
        self._pos = np.zeros(max_slots, np.int32)
        self._tok = np.zeros(max_slots, np.int32)  # last sampled token
        self._outputs: list[RequestOutput] = []
        self._clock = clock
        self._t0: Optional[float] = None

    # -- introspection ----------------------------------------------------
    @property
    def num_active(self) -> int:
        return sum(s is not None for s in self._slots)

    def free_slots(self) -> list:
        return [i for i, s in enumerate(self._slots) if s is None]

    def _now(self) -> float:
        if self._t0 is None:
            self._t0 = self._clock()
        return self._clock() - self._t0

    def _abs(self, rel: float) -> float:
        """Engine-relative seconds back to the clock's absolute domain —
        what the flight recorder's retroactive spans take.  (With an
        injected test clock the absolute values live in that clock's
        domain, not ``perf_counter``'s; spans stay internally consistent
        either way.)"""
        return (self._t0 or 0.0) + rel

    # -- request lifecycle ------------------------------------------------
    def submit(self, req: Request) -> None:
        """Enqueue a request, validating it against this engine's capacity
        *now* rather than failing later at admission: a prompt that cannot
        fit the per-slot cache (prompt + at least one generated token)
        raises :class:`PromptTooLongError`, and a full bounded queue
        raises :class:`~repro.serve.errors.EngineOverloadError`.  Traces
        fed through :meth:`run` get these converted to ``"rejected"``
        outputs instead — one bad request must not kill a serve loop."""
        S = int(req.prompt.size)
        if S > self.max_seq_len:
            raise PromptTooLongError(
                f"request {req.uid}: prompt length {S} exceeds the "
                f"per-slot capacity {self.max_seq_len} (prompt plus at "
                f"least one generated token must fit)"
            )
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            obs.event("overload_reject", "engine", uid=req.uid,
                      queue_depth=len(self.queue))
            # postmortem: dump the flight recorder before surfacing the
            # overload, so the timeline leading into it survives the crash
            obs.postmortem("EngineOverloadError")
            raise EngineOverloadError(
                f"request {req.uid}: queue is at its bound "
                f"({self.max_queue}); retry later or raise max_queue"
            )
        self.queue.push(req)

    def _reject(self, req: Request, now: float) -> None:
        self._outputs.append(RequestOutput(
            uid=req.uid, prompt_len=int(req.prompt.size), tokens=[],
            finish_reason="rejected", arrival_time=req.arrival_time,
            admitted_time=now, finish_time=self._now(), token_times=[],
            deadline=req.deadline,
        ))
        self.stats["rejected"] += 1
        obs.event("rejected", f"req:{req.uid}", uid=req.uid)

    def _finish_unserved(self, req: Request, now: float,
                         reason: str) -> None:
        """Terminal outcome for a request that never occupied a slot:
        ``"timeout"`` (deadline expired while queued / predicted blown at
        admission) or ``"shed"`` (the controller dropped it)."""
        self._outputs.append(RequestOutput(
            uid=req.uid, prompt_len=int(req.prompt.size), tokens=[],
            finish_reason=reason, arrival_time=req.arrival_time,
            admitted_time=now, finish_time=self._now(), token_times=[],
            deadline=req.deadline,
        ))
        self.stats[reason] += 1
        if obs.enabled():
            obs.complete("queued", self._abs(req.arrival_time),
                         self._abs(self._now()), f"req:{req.uid}",
                         uid=req.uid, outcome=reason)
            obs.event(reason, f"req:{req.uid}", uid=req.uid)

    def _admit(self, slot: int, req: Request, now: float) -> bool:
        """Prefill ``req`` into ``slot`` and sample its first token.
        Returns False (leaving the slot free and the cache untouched) when
        the paged pool cannot supply the prompt's pages; raises
        :class:`PromptTooLongError` for over-long prompts."""
        prompt = jnp.asarray(req.prompt, jnp.int32)[None]
        if self.faults is not None:
            self.faults.admission_delay()
        S = int(req.prompt.size)
        # the span ends after the first token's fetch, so it holds the
        # device's prefill and not only its enqueue
        with obs.span("engine.admit", "engine", uid=req.uid, prompt_len=S,
                      slot=slot):
            t_pre = self._now()
            if self.paged:
                logits = self.kv.admit(self.params, prompt, slot)
                if logits is None:
                    return False
            else:
                logits = self.kv.write_prefill(self.params, prompt, slot)
            if self._latency is not None:
                self._latency.observe_prefill(S, self._now() - t_pre)
            self.stats["attn_window_masked_rows"] += window_masked_rows(
                self.cfg, 0, S)
            if obs.enabled():
                # the request's lifecycle row: time spent queued (arrival
                # to admission)
                obs.complete("queued", self._abs(req.arrival_time),
                             self._abs(now), f"req:{req.uid}", uid=req.uid)
            # token i (1-based) is written to the cache at position
            # S + i - 1, so generating N tokens needs S + N - 1 <= max_seq_len
            max_new = min(req.max_new_tokens, self.max_seq_len - S + 1)
            st = _SlotState(
                req=req, tokens=[], token_times=[], admitted_time=now,
                rng=np.random.default_rng(req.sampling.seed),
                max_new=max_new,
            )
            tok = sample_token(np.asarray(logits[0]), req.sampling, st.rng)
        st.tokens.append(tok)
        st.token_times.append(self._now())
        self._slots[slot] = st
        self._pos[slot] = S
        self._tok[slot] = tok
        if self._stopped(st, tok):
            self._finish(slot)
        return True

    def _stopped(self, st: _SlotState, tok: int) -> bool:
        return tok in st.req.stop_tokens or len(st.tokens) >= st.max_new

    def _finish(self, slot: int) -> None:
        st = self._slots[slot]
        reason = "stop" if st.tokens[-1] in st.req.stop_tokens else "length"
        obs.event("finish", f"req:{st.req.uid}", uid=st.req.uid,
                  reason=reason, tokens=len(st.tokens))
        self._outputs.append(RequestOutput(
            uid=st.req.uid,
            prompt_len=int(st.req.prompt.size),
            tokens=list(st.tokens),
            finish_reason=reason,
            arrival_time=st.req.arrival_time,
            admitted_time=st.admitted_time,
            finish_time=self._now(),
            token_times=list(st.token_times),
            deadline=st.req.deadline,
        ))
        self._slots[slot] = None
        self._pos[slot] = 0
        self._tok[slot] = 0
        if self.paged:
            self.kv.release_slot(slot, zero=self.reset_freed_slots)
            self._force_single = False  # pages freed; chunks may fit again
        elif self.reset_freed_slots:
            self.kv.reset(slot)

    def _preempt(self, slot: int) -> None:
        """Evict an active slot mid-stream: free its pages and return its
        request to the queue head.  Generated tokens are discarded — the
        re-served request reproduces them exactly (greedy decoding is
        deterministic, and non-greedy requests restart their seeded RNG
        stream), so preemption is invisible in the outputs."""
        st = self._slots[slot]
        self.kv.release_slot(slot)
        self._slots[slot] = None
        self._pos[slot] = 0
        self._tok[slot] = 0
        self.queue.push_front(st.req)
        self.stats["preemptions"] += 1
        obs.event("preempt", f"req:{st.req.uid}", uid=st.req.uid, slot=slot,
                  tokens_discarded=len(st.tokens))

    def _ensure_decode_pages(self, active, n_steps: int):
        """Before a paged decode of ``n_steps``, make every active slot's
        write range mapped and private (allocating growth pages,
        copy-on-writing shared ones).  When the pool runs dry the
        *youngest* active slot is preempted and the rest retry — oldest
        requests keep their pages, matching the admission order the queue
        would re-serve anyway.  Returns the surviving slots, or None when
        a lone slot cannot fit a multi-step chunk (the caller then falls
        back to single-step decode, which needs at most one new page).  A
        lone slot that cannot get even one page is rejected outright —
        its prompt fits but prompt + one generated token cannot, and with
        nothing left to preempt it would requeue forever."""
        pending = sorted(active,
                         key=lambda s: (self._slots[s].admitted_time, s))
        ok: list = []
        while pending:
            slot = pending[0]
            if self.kv.ensure_writable_range(slot, int(self._pos[slot]),
                                             n_steps):
                ok.append(pending.pop(0))
                continue
            if not ok and len(pending) == 1:
                if n_steps > 1:
                    return None  # retry as single-step before evicting
                st = self._slots[slot]
                self.kv.release_slot(slot)
                self._slots[slot] = None
                self._pos[slot] = 0
                self._tok[slot] = 0
                self._reject(st.req, st.admitted_time)
                break
            self._preempt(pending.pop())
        return sorted(ok)

    # -- sparsity tiers ----------------------------------------------------
    def set_tier(self, idx: int, reason: Optional[str] = None) -> None:
        """Serve from tier ``idx``'s resident weight copy.  A pure pytree
        pointer swap: the jitted decode programs key their executables on
        param structure, so after :meth:`warm_tiers` this never
        recompiles (``trace_events()`` stays flat across switches).
        ``reason`` annotates the timeline event (the engine forwards the
        controller's last escalation reason)."""
        if self.tiers is None:
            raise ValueError("engine was built without tiers")
        if idx == self.tier_idx:
            return
        obs.event("tier_switch", "controller",
                  tier_from=self.tiers[self.tier_idx].spec.name,
                  tier_to=self.tiers[idx].spec.name,
                  reason=reason or "manual")
        self.params = self.tiers[idx].params
        self.tier_idx = idx
        self.stats["tier_switches"] += 1

    def warm_tiers(self, prompt_lens: Iterable[int] = (8,)) -> None:
        """Eagerly compile every (tier, program) the controller may run:
        each tier's prefill (per distinct prompt length), single-step
        decode, and every chunk size in ``self._chunk_sizes`` — by serving
        a tiny trace per (tier, chunk size) through throwaway engines that
        share this engine's module-level jit caches.  After this, tier
        switches and chunk shrinks at serve time are pointer swaps into
        already-compiled executables."""
        if self.tiers is None:
            return
        plens = sorted({int(p) for p in prompt_lens}) or [8]
        kw = dict(max_slots=self.max_slots, max_seq_len=self.max_seq_len,
                  paged=self.paged)
        if self.paged:
            kw.update(page_size=self.kv.page_size,
                      num_pages=self.kv.num_pages)
        for tier in self.tiers:
            for T in self._chunk_sizes:
                reqs = [Request(uid=-1 - i,
                                prompt=np.arange(1, plen + 1) % 7 + 1,
                                max_new_tokens=max(2, T + 1))
                        for i, plen in enumerate(plens)]
                # max_new > T forces the chunked path through a full chunk
                # plus the tail; a lone non-greedy request warms the
                # single-step program (T == 1 runs it directly)
                eng = ServeEngine(tier.params, self.cfg, decode_chunk=T,
                                  **kw)
                eng.run(reqs)

    # -- fault hooks -------------------------------------------------------
    def _fault_gate(self, step_idx: int) -> None:
        """Run the injector's pre-decode gate, retrying injected transient
        faults with capped exponential backoff.  A burst outlasting
        ``max_retries`` propagates — that is a real outage, not jitter."""
        f = self.faults
        if f is None:
            return
        attempt = 0
        while True:
            try:
                f.pre_decode(step_idx)
                return
            except InjectedFaultError:
                if attempt >= f.cfg.max_retries:
                    obs.event("fault_retries_exhausted", "faults",
                              step=step_idx, attempts=attempt)
                    raise
                self.stats["fault_retries"] += 1
                obs.event("fault_retry", "faults", step=step_idx,
                          attempt=attempt)
                f.sleep(min(f.cfg.backoff_s * (2 ** attempt),
                            f.cfg.backoff_cap_s))
                attempt += 1

    def _fault_post(self, step_idx: int, measured_s: float) -> None:
        if self.faults is not None:
            self.faults.post_decode(step_idx, measured_s)

    def _count_tokens(self, produced: int) -> None:
        if self.tokens_by_tier is not None and produced:
            self.tokens_by_tier[
                self.tiers[self.tier_idx].spec.name] += produced

    # -- the engine loop --------------------------------------------------
    def step(self) -> int:
        """One scheduler iteration: expire/shed queued work, let the SLO
        controller pick the degradation level, admit ready requests into
        free slots (all of them when steady, a rationed budget when
        degraded), then run one decode *chunk* over the batch
        (``decode_chunk`` steps device-resident when every active request
        is greedy, one host-paced step otherwise).  Returns the number of
        tokens produced (0 when the engine idled)."""
        with obs.span("engine.step", "engine", queue=len(self.queue)):
            now = self._now()
            produced = 0
            for req in self.queue.expired(now):
                self._finish_unserved(req, now, "timeout")
            ctrl = self._controller
            if ctrl is not None:
                ctrl.begin_step(now, len(self.queue))
                if self.tiers is not None:
                    self.set_tier(ctrl.tier_index,
                                  reason=f"slo:{ctrl.last_reason}")
                if ctrl.should_shed(len(self.queue)):
                    for req in self.queue.shed(ctrl.shed_keep()):
                        self._finish_unserved(req, now, "shed")
            free = self.free_slots()
            budget = len(free) if ctrl is None \
                else ctrl.admission_budget(len(free))
            while free and budget > 0:
                req = self.queue.pop_ready(now)
                if req is None:
                    break
                if req.deadline is not None and self._latency is not None:
                    # admission-time cost prediction: a request that cannot
                    # possibly finish inside its deadline times out now,
                    # without burning a slot on doomed work
                    est = self._latency.request_s(
                        int(req.prompt.size),
                        min(req.max_new_tokens,
                            self.max_seq_len - int(req.prompt.size) + 1))
                    if est == est and now + est > req.deadline:
                        self._finish_unserved(req, now, "timeout")
                        continue
                try:
                    admitted = self._admit(free[0], req, now)
                except PromptTooLongError:
                    self._reject(req, now)
                    continue  # slot stays free for the next ready request
                if not admitted:
                    # out of pages: the request returns to the queue head
                    # and admission stops — live slots are untouched, and
                    # pages will free up as active requests finish
                    self.queue.push_front(req)
                    self.stats["deferred_admissions"] += 1
                    break
                free.pop(0)
                budget -= 1
                produced += 1  # the first token sampled from prefill logits
            active = [i for i, s in enumerate(self._slots) if s is not None]
            self.stats["peak_active"] = max(self.stats["peak_active"],
                                            len(active))
            if active:
                T = self.decode_chunk if ctrl is None \
                    else ctrl.decode_chunk(self.decode_chunk)
                if (T <= 1 or self._decode_chunk is None
                        or self._force_single
                        or not all(self._slots[s].req.sampling.greedy
                                   for s in active)):
                    T = 1
                produced += self._decode_active(active, T)
            self._count_tokens(produced)
            return produced

    def _chunk_fn(self, T: int):
        """The jitted decode program for ``T`` steps: the single-step program
        for 1, the pre-bound default for the base chunk, the module-level
        cache (same compiled executables) for the controller's shrunk
        chunk."""
        if T == 1:
            return self._decode
        if T == self.decode_chunk:
            return self._decode_chunk
        if self.paged:
            return _jit_paged_decode_chunk(self.cfg, self.kv.page_size,
                                           self.kv.num_pages, T)
        return _jit_decode_chunk(self.cfg, T)

    def _decode_active(self, active, T: int) -> int:
        """One decode call over the active slots, then the host's token
        bookkeeping.  ``T > 1`` is the greedy fast path: ``T`` steps in one
        jit call with on-device argmax sampling and a single chunked host
        fetch.  ``T == 1`` is the per-token reference path: one decode step,
        host-side sampling.

        The device loop always runs the full fixed-length chunk (one
        compiled program, no per-remaining-budget recompiles); tokens a
        request produced past its stop token or budget are simply discarded
        on the host.  Overshoot cache writes land in positions of slots
        that are about to be freed and are either overwritten by the next
        occupant's prefill/decode writes or masked out by the per-slot
        valid-prefix attention mask, so they are never read."""
        with obs.span("engine.decode", "engine") as span:
            with obs.span("engine.decode.prepare", "engine"):
                if self.paged:
                    ready = self._ensure_decode_pages(active, T)
                    if ready is None:
                        # a lone slot can't fit a whole chunk's pages:
                        # degrade to the one-page-at-a-time path until a
                        # finish frees pages
                        self._force_single = True
                        T = 1
                        ready = self._ensure_decode_pages(
                            [i for i, s in enumerate(self._slots)
                             if s is not None], 1)
                    active = ready
                tok = jnp.asarray(self._tok[:, None])
                pos = jnp.asarray(self._pos)
                table = (self.kv.device_table(),) if self.paged else ()
            if not active:
                return 0
            step_idx = self._decode_calls
            self._decode_calls += 1
            span.set(call=step_idx, steps=T, n_active=len(active))
            self._fault_gate(step_idx)
            fn = self._chunk_fn(T)
            t0 = self._now()
            out, self.kv.data = fn(self.params, tok, self.kv.data, *table,
                                   pos)
            self.stats["decode_steps_chunked" if T > 1
                       else "decode_steps_single"] += T
            self.stats["attn_window_masked_rows"] += sum(
                window_masked_rows(self.cfg, int(self._pos[s]), T)
                for s in active)
            with obs.span("engine.decode.fetch", "engine"):
                # [T, max_slots] tokens or [max_slots, V] logits — one sync
                out = np.asarray(out)
            self._fault_post(step_idx, self._now() - t0)
            t1 = self._now()
            if self._controller is not None:
                self._controller.observe_decode(t1 - t0, T)
        with obs.span("engine.emit", "engine"):
            return self._emit(active, out, T, t0, t1)

    def _emit(self, active, out, T: int, t0: float, t1: float) -> int:
        """Append each active slot's new tokens — a chunk's on-device argmax
        tokens, or one token sampled on the host from a single step's
        logits — until its stop condition.  Per-token timestamps spread the
        measured call latency uniformly across a chunk's tokens (the
        stream's average decode cadence)."""
        produced = 0
        for slot in active:
            st = self._slots[slot]
            for t in range(T):
                if T == 1:
                    nxt = sample_token(out[slot], st.req.sampling, st.rng)
                    st.token_times.append(t1)
                else:
                    nxt = int(out[t, slot])
                    st.token_times.append(t0 + (t + 1) * (t1 - t0) / T)
                st.tokens.append(nxt)
                self._pos[slot] += 1
                self._tok[slot] = nxt
                produced += 1
                if self._stopped(st, nxt):
                    self._finish(slot)
                    break
        return produced

    def run(self, requests: Iterable[Request] = (),
            max_steps: int = 1_000_000) -> list:
        """Serve until the queue drains and every slot finishes.  Returns
        the :class:`RequestOutput`s finished *during this call* in uid
        order.  The engine keeps one wall-clock epoch across repeated
        ``run()``/``step()`` calls, so ``metrics()`` aggregates the full
        lifetime consistently (arrival_times are relative to the first
        call)."""
        first_new = len(self._outputs)
        for req in requests:
            try:
                self.submit(req)
            except ServeError:
                # one bad request (over-long prompt, full bounded queue)
                # must not kill a trace replay: it finishes as rejected
                self._reject(req, self._now())
        if self._t0 is None:
            self._t0 = self._clock()
        steps = 0
        while (len(self.queue) or self.num_active) and steps < max_steps:
            before = self.num_active
            self.step()
            steps += 1
            if not before and not self.num_active and len(self.queue):
                # everything idle but traffic still due: wait for it in
                # short sleeps while the clock advances; if an injected
                # clock does not self-advance (e.g. a frozen test clock),
                # warp virtual time to the arrival so the loop always
                # makes progress
                nxt = self.queue.next_arrival()
                with obs.span("engine.wait", "engine"):
                    while nxt is not None:
                        remaining = nxt - self._now()
                        if remaining <= 0:
                            break
                        t_before = self._clock()
                        time.sleep(min(remaining, 0.05))
                        if self._clock() <= t_before:
                            self._t0 -= remaining
                            break
        return sorted(self._outputs[first_new:], key=lambda o: o.uid)

    def metrics(self, *, label: str = "serve") -> ServeMetrics:
        wall = self._now() if self._t0 is not None else 0.0
        slo = self.slo
        return summarize(
            self._outputs, wall, label=label,
            slo_tpot_s=None if slo is None else slo.tpot_ms * 1e-3,
            slo_ttft_s=None if slo is None or slo.ttft_ms is None
            else slo.ttft_ms * 1e-3,
            tokens_by_tier=self.tokens_by_tier,
        )


def warmup_engine(params, cfg: ModelConfig, requests, *,
                  engine_kwargs: Optional[dict] = None,
                  tune: bool = False, tune_reps: int = 3) -> None:
    """Populate the jit caches (one slot-prefill per distinct prompt
    length + the decode step, for this param structure) by serving a tiny
    trace through a throwaway engine, so a measured run reports
    steady-state latency instead of compile stalls.

    With ``tune=True`` the warmup first autotunes the kernel routing for
    the *actual* shapes this engine will serve — each sparse weight's
    gemv/spmm crossover at the engine's decode width (``max_slots``) and
    the trace's prompt lengths — and activates the resulting
    :class:`~repro.tune.table.TuningTable` (merging into any already
    active), so the compilations this warmup triggers, and every
    subsequent engine trace, route through measured decisions instead of
    the shipped defaults.  Tuning must precede compilation because routing
    lookups happen at trace time; that ordering is the point of hanging
    the hook here."""
    ekw = dict(engine_kwargs or {})
    requests = list(requests)
    if tune and any(
        isinstance(leaf, GroupedNMTensor)
        for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, GroupedNMTensor))
    ):
        from repro.tune.bench import autotune_for_serving

        autotune_for_serving(
            params,
            max_slots=ekw.get("max_slots", DEFAULT_MAX_SLOTS),
            prompt_lens=sorted({int(r.prompt.size) for r in requests}) or [8],
            dtype=jnp.dtype(cfg.dtype),
            reps=tune_reps,
        )
    seen, warm = set(), []
    for r in requests:
        if r.prompt.size not in seen:
            seen.add(r.prompt.size)
            warm.append(Request(uid=-1 - len(warm), prompt=r.prompt,
                                max_new_tokens=2))
    ServeEngine(params, cfg, **ekw).run(warm)


def compare_dense_sparse(params, cfg: ModelConfig, requests, *,
                         nm: tuple = (1, 4, 16), gr: int = 64,
                         engine_kwargs: Optional[dict] = None,
                         warmup: bool = False, tune: bool = False):
    """Serve the same request trace with dense and n:m:g-sparse weights.

    Returns {'dense': (outputs, metrics), 'sparse': (outputs, metrics)} —
    the side-by-side numbers of the paper's Fig 11 serving scenario.
    ``warmup`` pre-compiles both variants so the metrics measure serving,
    not XLA compilation; ``tune`` additionally autotunes the sparse
    variant's kernel routing for the served shapes during its warmup (see
    :func:`warmup_engine`; the hook no-ops for the dense variant, which
    has no routed sparse weights)."""
    engine_kwargs = dict(engine_kwargs or {})
    requests = list(requests)
    results = {}
    for label, p in (
        ("dense", params),
        ("sparse", sparsify_for_serving(params, *nm, gr=gr)),
    ):
        if warmup:
            warmup_engine(p, cfg, requests, engine_kwargs=engine_kwargs,
                          tune=tune)
        eng = ServeEngine(p, cfg, **engine_kwargs)
        outs = eng.run(requests)
        results[label] = (outs, eng.metrics(label=label))
    return results
