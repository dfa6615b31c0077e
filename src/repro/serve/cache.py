"""Serving KV caches: the static-shape state behind continuous batching.

Two implementations share one contract (static shapes, per-slot positions,
admission via prefill, decode via the model's decode core):

* :class:`SlotKVCache` — the original slot-owns-a-full-row pool: one
  ``init_cache(cfg, max_slots, max_seq_len)`` pytree whose batch axis is a
  pool of *slots*.  A request occupies a slot from admission to
  completion; admission writes its prefill K/V into the slot via the
  model's ``prefill_into_slot`` entry point, decode advances every slot at
  its own position, and freed slots are simply overwritten by the next
  admission.  ``decode_attention`` masks each slot to its own valid
  prefix, so stale tail entries are never read.

* :class:`PagedKVCache` — the paged pool: sequence-bearing leaves are
  stored as ``[L, num_pages, page_size, ...]`` and each slot owns an int32
  row of a ``[max_slots, pages_per_slot]`` page table mapping its logical
  pages to physical ones (sentinel ``num_pages`` = unmapped).  Decode
  gathers a slot-major *view* through the table, runs the model's decode
  core over it read-only with the call's new rows in a small append
  buffer, and commits only those rows back through the table — so the
  XLA programs stay static-shape and no loop carries the view.  Requests
  admitted with a common prompt prefix refcount the same physical pages
  (copy-on-write; host bookkeeping in
  :class:`~repro.serve.queue.PageAllocator`), which is what lets a pool
  sized for N full sequences serve many times that many concurrent
  prefix-sharing requests.

Out-of-range writes are *dropped*, never clamped: unmapped / overshoot
destinations are redirected to the sentinel page index, which XLA scatter
discards (the same masked-overshoot contract the slot cache's chunked
decode relies on).  Gather clamps sentinel reads to a real page, but every
row a clamped read can produce lies beyond the slot's valid prefix and is
masked by ``decode_attention``.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import forward, init_cache, logits_of, prefill_into_slot
from repro.models.common import ModelConfig
# the slot writer's structural helpers: which cache leaves carry a seq
# axis, and the storage-dtype cast (int8 KV quantization)
from repro.models.transformer import _seq_leaf_kinds, _to_cache_dtype
# PromptTooLongError lives in the typed serve error family now; re-exported
# here because this module is where it historically came from
from repro.serve.errors import PromptTooLongError
from repro.serve.queue import PageAllocator, prefix_hashes
from repro.serve.tracecount import note_trace

__all__ = ["SlotKVCache", "PagedKVCache", "PromptTooLongError",
           "reset_slot", "gather_slots", "paged_view", "paged_commit"]


@functools.lru_cache(maxsize=16)
def _jit_slot_prefill(cfg: ModelConfig):
    """One jitted slot-prefill per config, shared across caches; jit then
    specializes per (prompt length, param structure).  The cache operand is
    donated: admission updates the slot pool in place instead of copying
    the whole [max_slots, max_seq_len] pytree.

    Bounded (unlike the read-only pattern tables in ``core/layouts.py``):
    each entry holds a jitted closure whose executable cache grows per
    traced prompt length, so an unbounded cache leaks compiled programs in
    a long-running engine that cycles through many configs.  Eviction of a
    cold config only costs a recompile if it returns."""

    def _prefill(p, toks, cache, slot, off):
        note_trace("slot_prefill")  # trace-time only: counts compilations
        return prefill_into_slot(p, cfg, toks, cache, slot,
                                 write_offset=off)

    return jax.jit(_prefill, donate_argnums=(2,))


@jax.jit
def reset_slot(cache, slot):
    """Zero batch row ``slot`` of every cache leaf."""
    return jax.tree_util.tree_map(
        lambda l: l.at[:, slot].set(jnp.zeros((), l.dtype)), cache
    )


@jax.jit
def gather_slots(cache, perm):
    """Reorder the slot axis by ``perm`` (int32 [max_slots]) — slot
    compaction.  Row i of the result is old row perm[i]."""
    return jax.tree_util.tree_map(lambda l: l[:, perm], cache)


class SlotKVCache:
    """Owns the slot-pool cache pytree plus per-slot host bookkeeping."""

    def __init__(self, cfg: ModelConfig, max_slots: int, max_seq_len: int,
                 *, enc_len: int = 0):
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.data: Any = init_cache(cfg, max_slots, max_seq_len,
                                    enc_len=enc_len)
        # one compiled slot-prefill per distinct prompt length (prompts are
        # not padded: padding would write pad-token K/V into the slot)
        self._prefill_jit = _jit_slot_prefill(cfg)

    def write_prefill(self, params, tokens, slot: int, *,
                      write_offset: int = 0):
        """Admit one request: prefill ``tokens`` [1, S] into ``slot`` at
        seq offset ``write_offset``.  Returns the last-position logits
        [1, V]."""
        assert tokens.ndim == 2 and tokens.shape[0] == 1
        if tokens.shape[1] > self.max_seq_len:
            raise PromptTooLongError(
                f"prompt ({tokens.shape[1]}) exceeds max_seq_len "
                f"({self.max_seq_len})"
            )
        logits, self.data = self._prefill_jit(
            params, tokens, self.data, jnp.asarray(slot, jnp.int32),
            jnp.asarray(write_offset, jnp.int32),
        )
        return logits

    def reset(self, slot: int) -> None:
        self.data = reset_slot(self.data, jnp.asarray(slot, jnp.int32))

    def compact(self, perm) -> None:
        self.data = gather_slots(self.data, jnp.asarray(perm, jnp.int32))


# ---------------------------------------------------------------------------
# paged cache: device-side pure functions
# ---------------------------------------------------------------------------


@jax.named_scope("kv.view")
def paged_view(cfg: ModelConfig, pool, table, page_size: int):
    """Gather the slot-major logical cache out of the paged pool.

    Seq leaves [L, num_pages, page_size, ...] become
    [L, max_slots, pages_per_slot * page_size, ...] by indexing with the
    (flattened) page table; state leaves (SSM states, cross K/V) are
    slot-indexed already and pass through.  Sentinel (unmapped) table
    entries clamp to a real page — the rows they produce sit beyond the
    slot's valid prefix and are masked by ``decode_attention``."""
    kinds = _seq_leaf_kinds(cfg, 0)
    B, pps = table.shape

    def leaf(l, is_seq):
        if not is_seq:
            return l
        npg = l.shape[1]
        flat = jnp.clip(table.reshape(-1), 0, npg - 1)
        v = l[:, flat]  # [L, B * pps, page_size, ...]
        return v.reshape((l.shape[0], B, pps * page_size) + l.shape[3:])

    return jax.tree_util.tree_map(leaf, pool, kinds)


@jax.named_scope("kv.commit")
def paged_commit(cfg: ModelConfig, pool, buf, table, pos, page_size: int,
                 num_pages: int):
    """Write back what a decode chunk produced: each slot's append-buffer
    rows (``buf`` sequence leaves [L, B, T, ...], positions ``pos ..
    pos+T-1``) are scattered straight into their physical pages; state
    leaves are taken wholesale from the buffer.

    Unmapped slots (sentinel table rows) and overshoot positions
    (``>= pages_per_slot * page_size``) resolve to the out-of-range page
    index ``num_pages``, which XLA scatter drops — the paged spelling of
    the slot cache's dropped out-of-range writes.  The engine guarantees
    every *mapped* destination page is private (refcount 1) before the
    chunk runs, so no two slots ever scatter into the same page."""
    kinds = _seq_leaf_kinds(cfg, 0)
    B, pps = table.shape
    S = pps * page_size

    def leaf(pl, bl, is_seq):
        if not is_seq:
            return bl
        t = jnp.arange(bl.shape[2], dtype=jnp.int32)
        wpos = pos[:, None] + t[None, :]                 # [B, T]
        safe = jnp.clip(wpos, 0, S - 1)
        phys = jnp.take_along_axis(table, safe // page_size, axis=1)
        phys = jnp.where(wpos < S, phys, num_pages)      # drop overshoot
        return pl.at[:, phys, safe % page_size].set(bl)

    return jax.tree_util.tree_map(leaf, pool, buf, kinds)


@functools.lru_cache(maxsize=16)
def _jit_copy_page(cfg: ModelConfig):
    """Copy-on-write primitive: duplicate physical page ``src`` into
    ``dst`` on every seq leaf (state leaves are per-slot, not paged)."""
    kinds = _seq_leaf_kinds(cfg, 0)

    def copy(pool, src, dst):
        return jax.tree_util.tree_map(
            lambda l, isq: l.at[:, dst].set(l[:, src]) if isq else l,
            pool, kinds,
        )

    return jax.jit(copy, donate_argnums=(0,))


@functools.lru_cache(maxsize=16)
def _jit_zero_pages(cfg: ModelConfig):
    """Zero a fixed-size batch of physical pages (sentinel entries are
    dropped by the scatter) — the paged analogue of ``reset_slot``."""
    kinds = _seq_leaf_kinds(cfg, 0)

    def zero(pool, pages):
        return jax.tree_util.tree_map(
            lambda l, isq: l.at[:, pages].set(jnp.zeros((), l.dtype))
            if isq else l,
            pool, kinds,
        )

    return jax.jit(zero, donate_argnums=(0,))


@functools.lru_cache(maxsize=16)
def _jit_gather_pages(cfg: ModelConfig):
    """Permute the physical-page axis (compaction)."""
    kinds = _seq_leaf_kinds(cfg, 0)

    def gather(pool, perm):
        return jax.tree_util.tree_map(
            lambda l, isq: l[:, perm] if isq else l, pool, kinds,
        )

    return jax.jit(gather, donate_argnums=(0,))


@functools.lru_cache(maxsize=16)
def _jit_paged_prefill(cfg: ModelConfig, page_size: int, num_pages: int):
    """Admission for the paged cache: run the collecting forward (the same
    graph ``prefill_into_slot`` traces), then scatter each token row of
    the contributions through the slot's page-table row.  Rows below
    ``start`` (the shared-prefix length) are redirected to the sentinel
    page and dropped — their physical pages already hold bitwise-identical
    K/V written by the first request that computed this prefix (causal
    attention: a position's K/V depends only on tokens at or before it).
    State leaves write batch row ``slot`` wholesale.  Jit specializes per
    prompt length, like the slot prefill."""

    def run(p, toks, pool, table_row, slot, start):
        note_trace("paged_prefill")  # trace-time only: counts compilations
        hidden, _, contribs, _ = forward(
            p, cfg, toks, remat="none", collect_cache=True,
        )
        logits = logits_of(p, cfg, hidden[:, -1:])[:, 0]
        S = toks.shape[1]
        pos = jnp.arange(S, dtype=jnp.int32)
        phys = table_row[pos // page_size]
        phys = jnp.where(pos >= start, phys, num_pages)  # drop shared rows
        rowi = pos % page_size
        kinds = _seq_leaf_kinds(cfg, 0)

        def leaf(pl, cl, is_seq):
            piece = _to_cache_dtype(cl[:, 0], pl.dtype)
            if not is_seq:
                return pl.at[:, slot].set(piece)
            return pl.at[:, phys, rowi].set(piece)   # [L, S, ...] rows

        with jax.named_scope("kv.prefill_write"):
            pool = jax.tree_util.tree_map(leaf, pool, contribs, kinds)
        return logits, pool

    return jax.jit(run, donate_argnums=(2,))


class PagedKVCache:
    """Paged KV pool + page table + host-side allocator/sharing state.

    Parameters
    ----------
    cfg, max_slots, max_seq_len : as for :class:`SlotKVCache` —
        ``max_seq_len`` is the per-slot *logical* capacity (page table
        width × page size), no longer a physical reservation.
    page_size : tokens per physical page; must divide ``max_seq_len``.
    num_pages : physical pool size.  Defaults to
        ``max_slots * max_seq_len / page_size`` — exactly the slot cache's
        memory — but the point of paging is that with prefix sharing and
        mixed prompt lengths the pool can be *oversubscribed*: many more
        slots than ``num_pages // pages_per_slot``.
    prefix_sharing : admit requests with a known prompt prefix onto the
        existing physical pages (refcounted, copy-on-write).

    Local/sliding-window layers are stored full-length (no ring
    truncation): a ring buffer would alias multiple logical positions onto
    one physical row, which is exactly what a page table cannot express.
    """

    SENTINEL_DOC = "unmapped table entries hold num_pages (out of range)"

    def __init__(self, cfg: ModelConfig, max_slots: int, max_seq_len: int,
                 *, page_size: int = 16, num_pages: Optional[int] = None,
                 prefix_sharing: bool = True):
        if max_seq_len % page_size:
            raise ValueError(
                f"max_seq_len ({max_seq_len}) must be a multiple of "
                f"page_size ({page_size})"
            )
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.page_size = page_size
        self.pages_per_slot = max_seq_len // page_size
        self.num_pages = (max_slots * self.pages_per_slot
                          if num_pages is None else int(num_pages))
        self.prefix_sharing = prefix_sharing
        self.alloc = PageAllocator(self.num_pages)
        # host-side page table; device copy is re-uploaded per decode call
        # (tiny: max_slots * pages_per_slot int32)
        self.table = np.full((max_slots, self.pages_per_slot),
                             self.num_pages, np.int32)
        self.data: Any = self._init_pool()
        self._prefill_jit = _jit_paged_prefill(cfg, page_size,
                                               self.num_pages)
        self._copy_jit = _jit_copy_page(cfg)
        self._zero_jit = _jit_zero_pages(cfg)
        self._gather_jit = _jit_gather_pages(cfg)
        self.stats = {"shared_tokens": 0, "prefilled_tokens": 0,
                      "cow_copies": 0, "peak_pages_in_use": 0}

    def _init_pool(self):
        """Seq leaves [L, num_pages, page_size, ...]; state leaves keep the
        slot-indexed [L, max_slots, ...] shape of the slot cache.  Built
        with ``local_window_cache=False`` so every seq leaf is full-length
        (see class docstring)."""
        kinds = _seq_leaf_kinds(self.cfg, 0)
        paged = init_cache(self.cfg, self.num_pages, self.page_size,
                           local_window_cache=False)
        slotted = init_cache(self.cfg, self.max_slots, self.page_size,
                             local_window_cache=False)
        return jax.tree_util.tree_map(
            lambda pg, st, isq: pg if isq else st, paged, slotted, kinds,
        )

    # -- introspection ----------------------------------------------------
    def device_table(self):
        return jnp.asarray(self.table)

    def slot_pages(self, slot: int) -> list:
        """Mapped (logical_page, physical_page) pairs for a slot."""
        row = self.table[slot]
        return [(j, int(p)) for j, p in enumerate(row)
                if p != self.num_pages]

    def logical_view(self):
        """Host-side helper (tests / debugging): the slot-major logical
        cache the decode step sees."""
        return paged_view(self.cfg, self.data, self.device_table(),
                          self.page_size)

    def _note_usage(self):
        used = self.alloc.pages_in_use()
        if used > self.stats["peak_pages_in_use"]:
            self.stats["peak_pages_in_use"] = used

    # -- admission --------------------------------------------------------
    def admit(self, params, tokens, slot: int):
        """Admit one request's prompt [1, S] into ``slot``: map shared
        prefix pages (refcount++), allocate private pages for the rest of
        the prompt, prefill, and scatter only the non-shared rows.

        Returns the last-position logits [1, V], or None when the pool
        cannot supply the private pages (the engine re-queues the request
        — admission never corrupts live slots).  Raises
        :class:`PromptTooLongError` beyond the logical capacity."""
        assert tokens.ndim == 2 and tokens.shape[0] == 1
        S = int(tokens.shape[1])
        if S > self.max_seq_len:
            raise PromptTooLongError(
                f"prompt ({S}) exceeds max_seq_len ({self.max_seq_len})"
            )
        assert np.all(self.table[slot] == self.num_pages), (
            f"slot {slot} admitted while still mapped"
        )
        toks_np = np.asarray(tokens[0])
        chain = (prefix_hashes(toks_np, self.page_size)
                 if self.prefix_sharing else [])
        shared: list = []
        shared_len = 0
        for digest, covered in chain:
            page = self.alloc.lookup_prefix(digest)
            if page is None:
                break
            shared.append((digest, page))
            shared_len = covered
        n_logical = -(-S // self.page_size)
        fresh = self.alloc.alloc(n_logical - len(shared))
        if fresh is None:
            return None  # out of pages; nothing increfed yet
        for _, page in shared:
            self.alloc.incref(page)
        row = self.table[slot]
        for j, (_, page) in enumerate(shared):
            row[j] = page
        for j, page in zip(range(len(shared), n_logical), fresh):
            row[j] = page
        # publish this prompt's prefix chain for future sharers (no-op for
        # digests already registered)
        for digest, covered in chain:
            row_idx = (covered - 1) // self.page_size
            self.alloc.register_prefix(digest, int(row[row_idx]))
        self._note_usage()
        self.stats["shared_tokens"] += shared_len
        self.stats["prefilled_tokens"] += S
        logits, self.data = self._prefill_jit(
            params, tokens, self.data, jnp.asarray(row),
            jnp.asarray(slot, jnp.int32), jnp.asarray(shared_len, jnp.int32),
        )
        return logits

    # -- decode-write preparation (allocation growth + copy-on-write) -----
    def ensure_writable_range(self, slot: int, start: int,
                              n_steps: int) -> bool:
        """Guarantee every page that decode positions
        ``start .. start+n_steps-1`` touch is mapped *and* private
        (refcount 1), copy-on-writing shared pages and allocating unmapped
        ones.  Returns False — leaving completed work in place, which is
        harmless (mapped pages stay refcounted to this slot) — when the
        pool runs dry; the engine then preempts a slot and retries."""
        lo = max(0, start)
        hi = min(start + n_steps, self.max_seq_len)
        for lp in sorted({p // self.page_size for p in range(lo, hi)}):
            phys = int(self.table[slot, lp])
            if phys == self.num_pages:
                got = self.alloc.alloc(1)
                if got is None:
                    return False
                self.table[slot, lp] = got[0]
            elif self.alloc.refcount[phys] > 1:
                got = self.alloc.alloc(1)
                if got is None:
                    return False
                self.data = self._copy_jit(
                    self.data, jnp.asarray(phys, jnp.int32),
                    jnp.asarray(got[0], jnp.int32),
                )
                self.alloc.decref(phys)
                self.table[slot, lp] = got[0]
                self.stats["cow_copies"] += 1
        self._note_usage()
        return True

    # -- release / reset / compaction -------------------------------------
    def release_slot(self, slot: int, *, zero: bool = False) -> list:
        """Unmap a slot, decref its pages; returns the physical pages this
        actually freed.  With ``zero`` the freed pages are also cleared on
        device (the isolation-test analogue of ``reset_slot``)."""
        freed = []
        for j in range(self.pages_per_slot):
            phys = int(self.table[slot, j])
            if phys == self.num_pages:
                continue
            self.table[slot, j] = self.num_pages
            if self.alloc.decref(phys):
                freed.append(phys)
        if zero and freed:
            pages = np.full(self.pages_per_slot, self.num_pages, np.int32)
            pages[:len(freed)] = freed
            self.data = self._zero_jit(self.data, jnp.asarray(pages))
        return freed

    def compact(self) -> None:
        """Pack live physical pages to the front of the pool, preserving
        their contents, and rewrite the table + allocator to match (e.g.
        before shrinking the pool)."""
        old_to_new = self.alloc.compaction_perm()
        perm = np.arange(self.num_pages, dtype=np.int32)
        for old, new in old_to_new.items():
            perm[new] = old
        self.data = self._gather_jit(self.data, jnp.asarray(perm))
        self.alloc.apply_compaction(old_to_new)
        for s in range(self.max_slots):
            for j in range(self.pages_per_slot):
                p = int(self.table[s, j])
                if p != self.num_pages:
                    self.table[s, j] = old_to_new[p]
