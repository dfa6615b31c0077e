"""Paged KV cache: differential equivalence against the slot cache (tokens
and bitwise KV contents), allocator invariants (property-based plus seeded
randomized fallbacks), copy-on-write prefix sharing, and compaction.

The load-bearing guarantee: the paged engine is *observationally
identical* to the slot engine — same tokens for every request under any
admission/eviction order — because decode runs the same decode core
(``decode_step_buffered``: a read-only history plus an append buffer of
the call's new rows) over a gathered slot-major view of the page pool.
These tests pin that down at both the cache layer (bitwise KV rows) and the
engine layer (token streams under oversubscription, sharing, preemption).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.configs import get_smoke
from repro.models import init_lm
from repro.serve import (
    PageAllocator,
    PagedKVCache,
    PromptTooLongError,
    Request,
    ServeEngine,
    SlotKVCache,
    prefix_hashes,
)
from repro.models.transformer import _seq_leaf_kinds
from repro.serve.engine import _jit_decode, _jit_paged_decode, \
    _jit_paged_decode_chunk

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke("bert-base-sten"), dtype="float32")
    params = init_lm(KEY, cfg)
    yield cfg, params
    # This module compiles many engine variants (page sizes x widths x
    # chunk lengths).  The tier-1 suite runs ~400 tests in one process;
    # dropping this module's executables keeps late XLA compiles from
    # running against a process full of retained programs.
    from repro.serve import cache as _cache, engine as _engine
    for mod in (_cache, _engine):
        for fn in vars(mod).values():
            clear = getattr(fn, "cache_clear", None)
            if clear is not None:
                clear()
    jax.clear_caches()


def make_prompt(length, seed=0, vocab=512):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (length,), 0, vocab, jnp.int32
    ))


def run_tokens(engine, reqs):
    return [(o.uid, o.tokens, o.finish_reason) for o in engine.run(reqs)]


def seq_rows(tree, slot, n):
    """The first ``n`` valid seq rows (and the state row) of one slot, as
    numpy — the bitwise comparison unit.  Seq leaves are [L, B, S, ...]
    (ndim >= 3 with the seq axis at 2); state leaves are [L, B, ...]."""
    out = []
    for leaf in jax.tree_util.tree_leaves(tree):
        a = np.asarray(leaf)
        out.append(a[:, slot, :n] if a.ndim >= 3 else a[:, slot])
    return out


# ---------------------------------------------------------------------------
# differential: paged == slot, tokens and bitwise KV
# ---------------------------------------------------------------------------


def _mixed_trace(vocab, n=6, base_seed=0):
    """Prompt-length mix spanning sub-page, page-aligned, and multi-page."""
    lens = [3, 8, 13, 16, 21, 5][:n]
    return [Request(uid=i, prompt=make_prompt(L, seed=base_seed + i,
                                              vocab=vocab),
                    max_new_tokens=4 + i % 3)
            for i, L in enumerate(lens)]


@pytest.mark.parametrize("page_size", [4, 8, 16])
def test_paged_matches_slot_tokens(setup, page_size):
    """Every request's token stream is identical between the slot cache
    and the paged cache, across page sizes and a prompt-length mix that
    exercises partial tail pages and multi-page prompts."""
    cfg, params = setup
    want = run_tokens(
        ServeEngine(params, cfg, max_slots=3, max_seq_len=32,
                    decode_chunk=4),
        _mixed_trace(cfg.vocab))
    got = run_tokens(
        ServeEngine(params, cfg, max_slots=3, max_seq_len=32,
                    decode_chunk=4, paged=True, page_size=page_size),
        _mixed_trace(cfg.vocab))
    assert got == want


def test_paged_matches_slot_across_eviction_orders(setup):
    """Slot reuse (more requests than slots) and mixed stop conditions:
    admission/eviction interleavings differ between runs but outputs do
    not."""
    cfg, params = setup
    def trace():
        reqs = _mixed_trace(cfg.vocab, base_seed=50)
        # an immediate finisher forces an extra early eviction + slot reuse
        reqs.append(Request(uid=9, prompt=make_prompt(7, seed=59,
                                                      vocab=cfg.vocab),
                            max_new_tokens=1))
        return reqs
    want = run_tokens(ServeEngine(params, cfg, max_slots=2, max_seq_len=32,
                                  decode_chunk=1), trace())
    got = run_tokens(ServeEngine(params, cfg, max_slots=2, max_seq_len=32,
                                 decode_chunk=1, paged=True, page_size=8),
                     trace())
    assert got == want


def test_paged_single_and_chunked_decode_agree(setup):
    """The paged chunked decode (scan over the gathered view, one commit)
    equals the per-token paged loop — the paged analogue of the slot
    engine's chunk-equivalence guarantee."""
    cfg, params = setup
    a = run_tokens(ServeEngine(params, cfg, max_slots=2, max_seq_len=32,
                               decode_chunk=1, paged=True, page_size=8),
                   _mixed_trace(cfg.vocab, base_seed=9))
    b = run_tokens(ServeEngine(params, cfg, max_slots=2, max_seq_len=32,
                               decode_chunk=4, paged=True, page_size=8),
                   _mixed_trace(cfg.vocab, base_seed=9))
    assert a == b


@pytest.mark.parametrize("page_size", [4, 8])
def test_paged_kv_bitwise_equals_slot(setup, page_size):
    """Drive both caches through admission + decode and compare the valid
    KV rows *bitwise*: the paged pool, read back through its page table,
    must hold exactly the bytes the slot cache holds."""
    cfg, params = setup
    S0, S1 = 11, 6
    p0 = jnp.asarray(make_prompt(S0, seed=1, vocab=cfg.vocab)[None])
    p1 = jnp.asarray(make_prompt(S1, seed=2, vocab=cfg.vocab)[None])

    sk = SlotKVCache(cfg, 2, 32)
    pk = PagedKVCache(cfg, 2, 32, page_size=page_size)
    lg_s0 = sk.write_prefill(params, p0, 0)
    lg_p0 = pk.admit(params, p0, 0)
    sk.write_prefill(params, p1, 1)
    pk.admit(params, p1, 1)
    np.testing.assert_array_equal(np.asarray(lg_s0), np.asarray(lg_p0))

    # greedy-decode both for a few steps with identical per-slot positions
    dec_s = _jit_decode(cfg)
    dec_p = _jit_paged_decode(cfg, pk.page_size, pk.num_pages)
    tok_s = np.asarray(
        [int(jnp.argmax(lg_s0[0]))] * 2, np.int32)  # slot 1 junk is masked
    tok_p = tok_s.copy()
    pos = np.asarray([S0, S1], np.int32)
    for step in range(5):
        assert pk.ensure_writable_range(0, int(pos[0]), 1)
        assert pk.ensure_writable_range(1, int(pos[1]), 1)
        ls, sk.data = dec_s(params, jnp.asarray(tok_s[:, None]), sk.data,
                            jnp.asarray(pos))
        lp, pk.data = dec_p(params, jnp.asarray(tok_p[:, None]), pk.data,
                            pk.device_table(), jnp.asarray(pos))
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(lp))
        tok_s = np.asarray(jnp.argmax(ls, -1), np.int32)
        tok_p = np.asarray(jnp.argmax(lp, -1), np.int32)
        pos = pos + 1

    view = pk.logical_view()
    for slot, valid in ((0, S0 + 5), (1, S1 + 5)):
        for a, b in zip(seq_rows(sk.data, slot, valid),
                        seq_rows(view, slot, valid)):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("page_size", [4, 8])
def test_paged_chunk_kv_bitwise_equals_single_step(setup, page_size):
    """A chunk of 4 (append buffer over the read-only view, one commit)
    leaves the pool, read back through the table, bitwise what 4 calls of
    the single-step program write, and samples the same tokens."""
    cfg, params = setup
    S0, S1, T = 11, 6, 4
    p0 = jnp.asarray(make_prompt(S0, seed=1, vocab=cfg.vocab)[None])
    p1 = jnp.asarray(make_prompt(S1, seed=2, vocab=cfg.vocab)[None])
    chunked = PagedKVCache(cfg, 2, 32, page_size=page_size)
    single = PagedKVCache(cfg, 2, 32, page_size=page_size)
    tok = []
    for kv in (chunked, single):
        tok = [int(jnp.argmax(kv.admit(params, p, slot)[0]))
               for slot, p in enumerate((p0, p1))]
    pos = np.asarray([S0, S1], np.int32)
    for kv in (chunked, single):
        for slot in (0, 1):
            assert kv.ensure_writable_range(slot, int(pos[slot]), T)

    chunk = _jit_paged_decode_chunk(cfg, page_size, chunked.num_pages, T)
    toks, chunked.data = chunk(params, jnp.asarray(tok, jnp.int32)[:, None],
                               chunked.data, chunked.device_table(),
                               jnp.asarray(pos))
    step = _jit_paged_decode(cfg, page_size, single.num_pages)
    t = np.asarray(tok, np.int32)
    for i in range(T):
        logits, single.data = step(params, jnp.asarray(t[:, None]),
                                   single.data, single.device_table(),
                                   jnp.asarray(pos + i))
        t = np.asarray(jnp.argmax(logits, -1), np.int32)
        np.testing.assert_array_equal(np.asarray(toks[i]), t)

    for slot, valid in ((0, S0 + T), (1, S1 + T)):
        for a, b in zip(seq_rows(chunked.logical_view(), slot, valid),
                        seq_rows(single.logical_view(), slot, valid)):
            np.testing.assert_array_equal(a, b)


def _scans(jaxpr):
    """Every ``scan`` equation in a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scans(sub)


def test_paged_chunk_scan_carries_no_view(setup):
    """The paged chunk program moves no copy of the gathered view through
    its loops: no carry of the chunk scan, and no output of the layer
    scan, has the shape of a sequence leaf of the view [L, B, S, ...].
    The chunk scan carries the [L, B, T, ...] append buffer instead."""
    cfg, params = setup
    T = 4
    kv = PagedKVCache(cfg, 2, 32, page_size=8)
    chunk = _jit_paged_decode_chunk(cfg, 8, kv.num_pages, T)
    jaxpr = jax.make_jaxpr(chunk)(
        params, jnp.zeros((2, 1), jnp.int32), kv.data, kv.device_table(),
        jnp.zeros(2, jnp.int32))
    view = jax.eval_shape(kv.logical_view)
    seq = {leaf.shape for leaf, is_seq in zip(
        jax.tree_util.tree_leaves(view),
        jax.tree_util.tree_leaves(_seq_leaf_kinds(cfg, 0))) if is_seq}
    assert seq
    buffer = {s[:2] + (T,) + s[3:] for s in seq}

    scans = list(_scans(jaxpr.jaxpr))
    chunk_scans = [e for e in scans if e.params["length"] == T]
    layer_scans = [e for e in scans if e.params["length"] == cfg.n_layers]
    assert len(chunk_scans) == 1 and layer_scans
    (loop,) = chunk_scans
    n0, nc = loop.params["num_consts"], loop.params["num_carry"]
    carry = {v.aval.shape for v in loop.invars[n0:n0 + nc]}
    assert not carry & seq, carry
    assert buffer <= carry, carry
    for layers in layer_scans:
        ys = {v.aval.shape for v in layers.outvars[layers.params["num_carry"]:]}
        assert not ys & seq, ys


FAMILIES = ["minicpm3-4b", "hymba-1.5b", "gemma2-9b", "mamba2-370m"]


@pytest.mark.parametrize("arch", FAMILIES)
def test_paged_and_chunked_match_per_token_across_families(arch):
    """MLA latents, hybrid attention + SSM state, local/global pairs with
    window-sized (ring) caches, and a pure state model all take the same
    append-buffer path: the slot and paged engines, chunked or not, give
    the per-token slot loop's tokens."""
    cfg = dataclasses.replace(get_smoke(arch), dtype="float32")
    params = init_lm(jax.random.PRNGKey(3), cfg)

    def trace():
        return [Request(uid=i, prompt=make_prompt(L, seed=90 + i,
                                                  vocab=cfg.vocab),
                        max_new_tokens=4 + 2 * (i % 3))
                for i, L in enumerate([3, 8, 5, 6])]

    kw = dict(max_slots=2, max_seq_len=16)
    want = run_tokens(ServeEngine(params, cfg, decode_chunk=1, **kw),
                      trace())
    for extra in (dict(decode_chunk=4),
                  dict(decode_chunk=4, paged=True, page_size=4),
                  dict(decode_chunk=1, paged=True, page_size=8)):
        got = run_tokens(ServeEngine(params, cfg, **kw, **extra), trace())
        assert got == want, extra


def test_admission_order_does_not_leak_between_slots(setup):
    """Admitting request B after A (into a pool where A's pages are
    interleaved with B's) leaves A's rows bitwise untouched — the paged
    reuse of the slot-isolation guarantee."""
    cfg, params = setup
    pk = PagedKVCache(cfg, 3, 32, page_size=4)
    pa = jnp.asarray(make_prompt(10, seed=3, vocab=cfg.vocab)[None])
    pb = jnp.asarray(make_prompt(7, seed=4, vocab=cfg.vocab)[None])
    pk.admit(params, pa, 0)
    before = seq_rows(pk.logical_view(), 0, 10)
    pk.admit(params, pb, 1)
    pk.release_slot(1)
    pk.admit(params, pb, 2)  # reuses slot-1's just-freed pages
    after = seq_rows(pk.logical_view(), 0, 10)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# prefix sharing + copy-on-write
# ---------------------------------------------------------------------------


def test_shared_prefix_outputs_identical_to_unshared(setup):
    """Requests with a common prompt prefix served with sharing on must
    produce exactly the outputs of the sharing-off engine, while actually
    sharing pages (shared_tokens > 0, fewer pages used)."""
    cfg, params = setup
    prefix = make_prompt(12, seed=30, vocab=cfg.vocab)
    def trace():
        return [Request(uid=i,
                        prompt=np.concatenate(
                            [prefix, make_prompt(3 + i, seed=60 + i,
                                                 vocab=cfg.vocab)]),
                        max_new_tokens=4) for i in range(4)]
    on = ServeEngine(params, cfg, max_slots=4, max_seq_len=32,
                     decode_chunk=4, paged=True, page_size=4)
    off = ServeEngine(params, cfg, max_slots=4, max_seq_len=32,
                      decode_chunk=4, paged=True, page_size=4,
                      prefix_sharing=False)
    got_on = run_tokens(on, trace())
    got_off = run_tokens(off, trace())
    assert got_on == got_off
    assert on.kv.stats["shared_tokens"] > 0
    assert off.kv.stats["shared_tokens"] == 0
    assert (on.kv.stats["peak_pages_in_use"]
            < off.kv.stats["peak_pages_in_use"])


def test_decode_write_into_shared_page_copies_on_write(setup):
    """Two identical prompts share every page including the partial tail;
    the second slot's first decode-range write must CoW the tail page and
    leave the sibling's pages bitwise untouched."""
    cfg, params = setup
    prompt = jnp.asarray(make_prompt(10, seed=31, vocab=cfg.vocab)[None])
    pk = PagedKVCache(cfg, 2, 32, page_size=4)
    pk.admit(params, prompt, 0)
    pk.admit(params, prompt, 1)
    tail = 10 // 4  # logical page of the partial tail
    assert int(pk.table[0, tail]) == int(pk.table[1, tail])
    assert pk.alloc.refcount[int(pk.table[1, tail])] == 2
    before = seq_rows(pk.logical_view(), 0, 10)

    assert pk.ensure_writable_range(1, 10, 2)
    assert pk.stats["cow_copies"] == 1
    assert int(pk.table[0, tail]) != int(pk.table[1, tail])
    # sibling bitwise untouched; sharer's copy holds identical valid rows
    after0 = seq_rows(pk.logical_view(), 0, 10)
    after1 = seq_rows(pk.logical_view(), 1, 10)
    for a, b, c in zip(before, after0, after1):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_evicting_one_sharer_keeps_the_others_pages(setup):
    """Releasing one of two prefix-sharers must free nothing the survivor
    references; releasing the survivor then frees everything."""
    cfg, params = setup
    prompt = jnp.asarray(make_prompt(9, seed=32, vocab=cfg.vocab)[None])
    pk = PagedKVCache(cfg, 2, 32, page_size=4)
    pk.admit(params, prompt, 0)
    pk.admit(params, prompt, 1)
    survivor_pages = [p for _, p in pk.slot_pages(1)]
    before = seq_rows(pk.logical_view(), 1, 9)
    assert pk.release_slot(0) == []          # all pages still referenced
    for p in survivor_pages:
        assert pk.alloc.refcount[p] == 1
    for a, b in zip(before, seq_rows(pk.logical_view(), 1, 9)):
        np.testing.assert_array_equal(a, b)
    assert sorted(pk.release_slot(1)) == sorted(survivor_pages)
    assert pk.alloc.pages_in_use() == 0


def test_prefix_hash_chain_semantics():
    """Page j's digest commits to pages 0..j (chained), so a prompt that
    diverges at page k shares digests for pages < k only; the partial tail
    digest commits to the whole prompt (exact-match sharing only)."""
    a = np.arange(20, dtype=np.int32)
    b = a.copy(); b[9] = 999          # diverge inside page 2 (ps=4)
    ha, hb = prefix_hashes(a, 4), prefix_hashes(b, 4)
    assert [h for h, _ in ha[:2]] == [h for h, _ in hb[:2]]
    assert all(x != y for (x, _), (y, _) in zip(ha[2:], hb[2:]))
    assert [n for _, n in ha] == [4, 8, 12, 16, 20]
    # partial tail: covered_len is the full prompt length
    ht = prefix_hashes(a[:18], 4)
    assert [n for _, n in ht] == [4, 8, 12, 16, 18]
    # tail digest differs from the full-page digest of a longer prompt
    assert ht[-1][0] != ha[-1][0]


# ---------------------------------------------------------------------------
# allocator invariants — hypothesis properties + seeded fallbacks
# ---------------------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 16), st.lists(st.integers(0, 5), max_size=30))
def test_alloc_never_double_allocates(num_pages, sizes):
    """Property: across arbitrary alloc sequences, no live page is ever
    handed out twice, and failed allocs leave the pool untouched."""
    al = PageAllocator(num_pages)
    live = set()
    for n in sizes:
        free_before = al.num_free
        got = al.alloc(n)
        if got is None:
            assert n > free_before
            assert al.num_free == free_before
            continue
        assert len(got) == n and not (set(got) & live)
        live |= set(got)
        assert al.num_free + al.pages_in_use() == num_pages


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 12), st.data())
def test_refcount_frees_exactly_at_zero(num_pages, data):
    """Property: decref frees a page exactly when its model refcount hits
    zero — never before (shared pages survive) and never after."""
    al = PageAllocator(num_pages)
    model = {}
    for _ in range(40):
        op = data.draw(st.sampled_from(["alloc", "incref", "decref"]))
        if op == "alloc":
            got = al.alloc(1)
            if got is not None:
                model[got[0]] = 1
        elif op == "incref" and model:
            p = data.draw(st.sampled_from(sorted(model)))
            al.incref(p)
            model[p] += 1
        elif op == "decref" and model:
            p = data.draw(st.sampled_from(sorted(model)))
            model[p] -= 1
            freed = al.decref(p)
            assert freed == (model[p] == 0)
            if freed:
                del model[p]
        assert al.pages_in_use() == len(model)


def test_allocator_randomized_invariants():
    """Seeded randomized equivalent of the hypothesis properties above —
    runs in environments without hypothesis so the invariants are always
    exercised."""
    rng = np.random.default_rng(0)
    for trial in range(20):
        num_pages = int(rng.integers(1, 16))
        al = PageAllocator(num_pages)
        model = {}
        for _ in range(60):
            op = rng.choice(["alloc", "incref", "decref", "burst"])
            if op in ("alloc", "burst"):
                n = 1 if op == "alloc" else int(rng.integers(0, 6))
                free_before = al.num_free
                got = al.alloc(n)
                if got is None:
                    assert n > free_before and al.num_free == free_before
                else:
                    assert len(set(got)) == n
                    assert not (set(got) & set(model))
                    for p in got:
                        model[p] = 1
            elif op == "incref" and model:
                p = int(rng.choice(sorted(model)))
                al.incref(p)
                model[p] += 1
            elif op == "decref" and model:
                p = int(rng.choice(sorted(model)))
                model[p] -= 1
                assert al.decref(p) == (model[p] == 0)
                if model[p] == 0:
                    del model[p]
            assert al.pages_in_use() == len(model)
            assert al.num_free + al.pages_in_use() == num_pages
        for p in sorted(model):
            for _ in range(model[p] - 1):
                assert not al.decref(p)
            assert al.decref(p)
        assert al.pages_in_use() == 0 and al.num_free == num_pages


def test_prefix_index_never_resurrects_freed_pages():
    """A prefix-hash entry dies with its page: after free + realloc, the
    old digest must not resolve to the recycled page."""
    al = PageAllocator(1)  # single page: realloc must recycle it
    (p,) = al.alloc(1)
    al.register_prefix(b"digest-a", p)
    assert al.lookup_prefix(b"digest-a") == p
    assert al.decref(p)
    assert al.lookup_prefix(b"digest-a") is None
    (q,) = al.alloc(1)  # recycles the same physical page
    assert q == p and al.lookup_prefix(b"digest-a") is None


def test_compaction_preserves_live_page_contents(setup):
    """Compacting a fragmented pool packs live pages to the front while
    every slot's logical rows stay bitwise identical, the allocator's
    refcounts follow the move, and prefix sharing still works after."""
    cfg, params = setup
    pk = PagedKVCache(cfg, 4, 16, page_size=4)
    prompts = [jnp.asarray(make_prompt(6 + 3 * i, seed=70 + i,
                                       vocab=cfg.vocab)[None])
               for i in range(4)]
    for i, p in enumerate(prompts):
        pk.admit(params, p, i)
    pk.release_slot(0)
    pk.release_slot(2)  # fragment the pool
    lens = {1: prompts[1].shape[1], 3: prompts[3].shape[1]}
    before = {s: seq_rows(pk.logical_view(), s, n) for s, n in lens.items()}
    used_before = pk.alloc.pages_in_use()

    pk.compact()

    assert pk.alloc.pages_in_use() == used_before
    live = sorted(p for s in (1, 3) for _, p in pk.slot_pages(s))
    assert live == list(range(used_before))  # packed to the front
    for s, n in lens.items():
        for a, b in zip(before[s], seq_rows(pk.logical_view(), s, n)):
            np.testing.assert_array_equal(a, b)
    # the prefix index survived the renumbering: an identical prompt
    # re-admitted after compaction shares the survivor's pages
    pk.admit(params, prompts[1], 0)
    assert pk.stats["shared_tokens"] >= prompts[1].shape[1]


# ---------------------------------------------------------------------------
# typed admission errors
# ---------------------------------------------------------------------------


def test_prompt_too_long_raises_typed_error(setup):
    """Both caches raise PromptTooLongError (a ValueError, not a bare
    AssertionError) for over-capacity prompts — the regression for the
    admission assert that used to kill the serve loop."""
    cfg, params = setup
    long = jnp.asarray(make_prompt(40, seed=80, vocab=cfg.vocab)[None])
    with pytest.raises(PromptTooLongError):
        SlotKVCache(cfg, 2, 32).write_prefill(params, long, 0)
    with pytest.raises(PromptTooLongError):
        PagedKVCache(cfg, 2, 32, page_size=8).admit(params, long, 0)
    assert issubclass(PromptTooLongError, ValueError)


def test_hypothesis_marker():
    """Record (not assert) whether the property tests above ran under real
    hypothesis or as skipped stubs — visible in -v output either way."""
    assert HAVE_HYPOTHESIS in (True, False)
