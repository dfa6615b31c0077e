"""Continuous-batching engine tests: slot admission/eviction, mid-stream
arrival, stop conditions, sparse-weight serving, and the serving-equivalence
guarantee (engine output == the classic one-shot prefill+decode loop) that
guards the ``prefill``/``decode_step`` slot refactor."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import decode_step, init_lm, prefill
from repro.serve import (
    Request,
    RequestQueue,
    SamplingParams,
    ServeEngine,
    compare_dense_sparse,
    sample_token,
)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke("bert-base-sten"), dtype="float32")
    params = init_lm(KEY, cfg)
    yield cfg, params
    # drop this module's compiled engine variants (same rationale as
    # test_paged_cache.py: keep the long single-process suite from
    # accumulating executables)
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


def oneshot_greedy(params, cfg, prompt, gen_len):
    """The pre-engine serving loop: prefill + scalar-pos greedy decode."""
    S = prompt.size
    logits, cache = prefill(params, cfg, jnp.asarray(prompt)[None],
                            cache_len=S + gen_len)
    tok = int(jnp.argmax(logits, -1)[0])
    out = [tok]
    for i in range(gen_len - 1):
        logits, cache = decode_step(
            params, cfg, jnp.asarray([[tok]], jnp.int32), cache,
            jnp.asarray(S + i),
        )
        tok = int(jnp.argmax(logits, -1)[0])
        out.append(tok)
    return out


# ---------------------------------------------------------------------------
# serving equivalence — the refactor guard
# ---------------------------------------------------------------------------


def test_engine_matches_oneshot_single_request(setup):
    """A single greedy request through the slot engine must reproduce the
    one-shot loop token for token (pinned seed)."""
    cfg, params = setup
    prompt = make_prompt(12, seed=7, vocab=cfg.vocab)
    want = oneshot_greedy(params, cfg, prompt, gen_len=6)

    eng = ServeEngine(params, cfg, max_slots=4, max_seq_len=18)
    outs = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=6)])
    assert len(outs) == 1
    assert outs[0].tokens == want
    assert outs[0].finish_reason == "length"


def test_engine_matches_oneshot_under_batching(setup):
    """Slot isolation: a request's tokens are identical whether it is served
    alone or alongside unrelated traffic in other slots."""
    cfg, params = setup
    prompt = make_prompt(10, seed=3, vocab=cfg.vocab)
    want = oneshot_greedy(params, cfg, prompt, gen_len=5)

    others = [Request(uid=10 + i, prompt=make_prompt(6 + i, seed=100 + i,
                                                     vocab=cfg.vocab),
                      max_new_tokens=7) for i in range(3)]
    eng = ServeEngine(params, cfg, max_slots=4, max_seq_len=16)
    outs = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=5)] + others)
    got = next(o for o in outs if o.uid == 0)
    assert got.tokens == want


def test_chunked_decode_matches_per_token_reference(setup):
    """The jitted multi-token decode chunk (on-device greedy sampling, one
    host fetch per chunk) produces exactly the tokens of the per-token
    host-paced loop, across mixed budgets, stop tokens, and slot reuse."""
    cfg, params = setup
    prompt = make_prompt(10, seed=5, vocab=cfg.vocab)
    stop = oneshot_greedy(params, cfg, prompt, gen_len=6)[2]
    reqs = lambda: [  # noqa: E731
        Request(uid=0, prompt=make_prompt(12, seed=7, vocab=cfg.vocab),
                max_new_tokens=9),
        Request(uid=1, prompt=make_prompt(6, seed=8, vocab=cfg.vocab),
                max_new_tokens=3),
        Request(uid=2, prompt=prompt, max_new_tokens=6,
                stop_tokens=(stop,)),
        Request(uid=3, prompt=make_prompt(5, seed=9, vocab=cfg.vocab),
                max_new_tokens=7),
    ]
    ref = ServeEngine(params, cfg, max_slots=2, max_seq_len=24,
                      decode_chunk=1).run(reqs())
    got = ServeEngine(params, cfg, max_slots=2, max_seq_len=24,
                      decode_chunk=4).run(reqs())
    assert [(o.uid, o.tokens, o.finish_reason) for o in got] == \
        [(o.uid, o.tokens, o.finish_reason) for o in ref]


def test_non_greedy_requests_take_host_path(setup):
    """A non-greedy request in the batch falls back to the per-token loop,
    keeping seeded sampling reproducible under chunked engines."""
    cfg, params = setup
    prompt = make_prompt(8, seed=11, vocab=cfg.vocab)
    sp = SamplingParams(greedy=False, temperature=0.7, top_k=8, seed=42)
    mk = lambda: [Request(uid=0, prompt=prompt, max_new_tokens=5,  # noqa: E731
                          sampling=sp),
                  Request(uid=1, prompt=make_prompt(6, seed=12,
                                                    vocab=cfg.vocab),
                          max_new_tokens=5)]
    a = ServeEngine(params, cfg, max_slots=2, max_seq_len=14,
                    decode_chunk=8).run(mk())
    b = ServeEngine(params, cfg, max_slots=2, max_seq_len=14,
                    decode_chunk=1).run(mk())
    assert [o.tokens for o in a] == [o.tokens for o in b]


@pytest.mark.parametrize("paged", [False, True], ids=["slot", "paged"])
def test_decode_step_counters_split_chunked_from_single(setup, paged):
    """``decode_steps_chunked`` counts the steps of the append-buffer chunk
    program, ``decode_steps_single`` those of the one-step fallback, and
    both mirror into the ``engine_stats`` registry family.  A greedy run
    only chunks; a non-greedy request forces one-step decode while it is
    active, then the greedy one left over chunks again."""
    from repro.obs.registry import REGISTRY

    cfg, params = setup
    fam = REGISTRY.family("engine_stats")
    kw = dict(max_slots=2, max_seq_len=32, decode_chunk=4, paged=paged,
              page_size=8)
    sp = SamplingParams(greedy=False, temperature=0.7, top_k=8, seed=7)

    before = dict(fam)
    greedy = ServeEngine(params, cfg, **kw)
    greedy.run([Request(uid=0, prompt=make_prompt(6, seed=40,
                                                  vocab=cfg.vocab),
                        max_new_tokens=9)])
    # the admission samples token 1; two chunks of 4 sample the other 8
    assert greedy.stats["decode_steps_chunked"] == 8
    assert greedy.stats["decode_steps_single"] == 0

    mixed = ServeEngine(params, cfg, **kw)
    mixed.run([Request(uid=0, prompt=make_prompt(6, seed=41,
                                                 vocab=cfg.vocab),
                       max_new_tokens=5, sampling=sp),
               Request(uid=1, prompt=make_prompt(7, seed=42,
                                                 vocab=cfg.vocab),
                       max_new_tokens=13)])
    # 4 one-step calls while the sampled request is active, then the
    # greedy one's last 8 tokens in two chunks
    assert mixed.stats["decode_steps_single"] == 4
    assert mixed.stats["decode_steps_chunked"] == 8
    for key in ("decode_steps_chunked", "decode_steps_single"):
        assert fam[key] - before.get(key, 0) == (greedy.stats[key]
                                                 + mixed.stats[key])


# ---------------------------------------------------------------------------
# scheduling: admission, eviction, mid-stream arrival
# ---------------------------------------------------------------------------


def test_more_requests_than_slots(setup):
    """8 requests through 2 slots: all finish, slots are reused (evicted
    and overwritten), outputs keep their request identity."""
    cfg, params = setup
    reqs = [Request(uid=i, prompt=make_prompt(6 + i % 3, seed=i,
                                              vocab=cfg.vocab),
                    max_new_tokens=4) for i in range(8)]
    eng = ServeEngine(params, cfg, max_slots=2, max_seq_len=16)
    outs = eng.run(reqs)
    assert [o.uid for o in outs] == list(range(8))
    assert all(len(o.tokens) == 4 for o in outs)
    assert eng.num_active == 0 and len(eng.free_slots()) == 2


def test_slot_reset_does_not_change_results(setup):
    """Explicit slot zeroing between occupants (reset_freed_slots) must not
    change any request's output — proving freed-slot garbage is never
    read."""
    cfg, params = setup
    reqs = [Request(uid=i, prompt=make_prompt(5 + i % 2, seed=40 + i,
                                              vocab=cfg.vocab),
                    max_new_tokens=5) for i in range(6)]
    ref = ServeEngine(params, cfg, max_slots=2, max_seq_len=12).run(reqs)
    got = ServeEngine(params, cfg, max_slots=2, max_seq_len=12,
                      reset_freed_slots=True).run(reqs)
    assert [o.tokens for o in got] == [o.tokens for o in ref]


def test_mid_stream_arrival(setup):
    """A request that arrives while others are decoding is admitted into a
    free slot mid-stream and still matches its solo output."""
    cfg, params = setup
    late_prompt = make_prompt(8, seed=77, vocab=cfg.vocab)
    want = oneshot_greedy(params, cfg, late_prompt, gen_len=4)

    # deterministic virtual clock: each call advances 1ms, so the late
    # arrival lands after several decode steps
    t = {"now": 0.0}

    def clock():
        t["now"] += 1e-3
        return t["now"]

    early = [Request(uid=i, prompt=make_prompt(6, seed=i, vocab=cfg.vocab),
                     max_new_tokens=12) for i in range(2)]
    late = Request(uid=9, prompt=late_prompt, max_new_tokens=4,
                   arrival_time=0.02)
    eng = ServeEngine(params, cfg, max_slots=3, max_seq_len=20, clock=clock)
    outs = eng.run(early + [late])
    got = next(o for o in outs if o.uid == 9)
    assert got.tokens == want
    assert got.admitted_time > outs[0].admitted_time  # genuinely later


# ---------------------------------------------------------------------------
# stop conditions and sampling
# ---------------------------------------------------------------------------


def test_stop_token_ends_generation(setup):
    """Generation ends at the first stop token.  Discover what greedy
    decoding produces, then re-serve with that token as a stop."""
    cfg, params = setup
    prompt = make_prompt(10, seed=5, vocab=cfg.vocab)
    free = oneshot_greedy(params, cfg, prompt, gen_len=6)
    stop = free[2]  # stop at the third generated token

    eng = ServeEngine(params, cfg, max_slots=2, max_seq_len=16)
    outs = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=6,
                            stop_tokens=(stop,))])
    assert outs[0].finish_reason == "stop"
    assert outs[0].tokens == free[:3]


def test_max_new_tokens_clamped_to_cache(setup):
    """A budget larger than the slot capacity finishes with 'length' at
    exactly the cache-capacity token count."""
    cfg, params = setup
    prompt = make_prompt(8, seed=9, vocab=cfg.vocab)
    eng = ServeEngine(params, cfg, max_slots=1, max_seq_len=12)
    outs = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=100)])
    # S + N - 1 <= max_seq_len  =>  N = 12 - 8 + 1 = 5
    assert len(outs[0].tokens) == 5
    assert outs[0].finish_reason == "length"


def test_sampling_reproducible_and_stop_immediate(setup):
    """Per-request seeded sampling is reproducible across runs; a
    max_new_tokens=1 request finishes straight from prefill."""
    cfg, params = setup
    prompt = make_prompt(8, seed=11, vocab=cfg.vocab)
    sp = SamplingParams(greedy=False, temperature=0.7, top_k=8, seed=123)
    req = lambda: Request(uid=0, prompt=prompt, max_new_tokens=6,  # noqa: E731
                          sampling=sp)
    a = ServeEngine(params, cfg, max_slots=2, max_seq_len=16).run([req()])
    b = ServeEngine(params, cfg, max_slots=2, max_seq_len=16).run([req()])
    assert a[0].tokens == b[0].tokens

    one = ServeEngine(params, cfg, max_slots=2, max_seq_len=16).run(
        [Request(uid=1, prompt=prompt, max_new_tokens=1)]
    )
    assert len(one[0].tokens) == 1 and one[0].finish_reason == "length"


def test_sample_token_top_k():
    rng = np.random.default_rng(0)
    logits = np.array([0.0, 5.0, 4.0, -1.0], np.float32)
    # top_k=1 degenerates to argmax regardless of temperature
    for _ in range(5):
        assert sample_token(logits, SamplingParams(greedy=False,
                                                   temperature=2.0, top_k=1),
                            rng) == 1
    # greedy ignores rng entirely
    assert sample_token(logits, SamplingParams(greedy=True), rng) == 1


def test_request_queue_arrival_order():
    q = RequestQueue()
    q.push(Request(uid=0, prompt=np.array([1]), arrival_time=0.5))
    q.push(Request(uid=1, prompt=np.array([1]), arrival_time=1.5))
    assert q.pop_ready(0.0) is None
    assert q.next_arrival() == 0.5
    assert q.pop_ready(1.0).uid == 0
    assert q.pop_ready(1.0) is None  # uid=1 not yet due
    assert q.pop_ready(2.0).uid == 1
    assert len(q) == 0


def test_request_queue_out_of_order_submission():
    """A due request is handed out even when it was submitted behind a
    not-yet-due one, and next_arrival reports the true minimum."""
    q = RequestQueue()
    q.push(Request(uid=0, prompt=np.array([1]), arrival_time=10.0))
    q.push(Request(uid=1, prompt=np.array([1]), arrival_time=0.0))
    assert q.next_arrival() == 0.0
    assert q.pop_ready(0.0).uid == 1
    assert q.pop_ready(0.0) is None
    assert q.pop_ready(10.0).uid == 0


# ---------------------------------------------------------------------------
# sparse path + metrics
# ---------------------------------------------------------------------------


def test_sparse_engine_serves_and_reports(setup):
    """The engine serves GroupedNMTensor params end to end and the
    dense-vs-sparse comparison yields valid side-by-side metrics."""
    cfg, params = setup
    reqs = [Request(uid=i, prompt=make_prompt(6, seed=i, vocab=cfg.vocab),
                    max_new_tokens=3) for i in range(3)]
    results = compare_dense_sparse(
        params, cfg, reqs, nm=(1, 4, 16),
        engine_kwargs=dict(max_slots=2, max_seq_len=10),
    )
    for label in ("dense", "sparse"):
        outs, met = results[label]
        assert len(outs) == 3
        assert met.num_tokens == 9
        assert met.tok_latency_p50 >= 0.0
        assert np.isfinite(met.throughput_tok_s)
        d = met.to_dict()
        assert {"ttft_p50", "ttft_p99", "tok_latency_p50",
                "tok_latency_p99", "throughput_tok_s"} <= set(d)
    # sparse serving really decoded different weights but same scheduler
    assert [o.prompt_len for o in results["dense"][0]] == \
        [o.prompt_len for o in results["sparse"][0]]


# ---------------------------------------------------------------------------
# slot-write semantics: offsets, ring alignment, frozen clocks
# ---------------------------------------------------------------------------


def test_write_slot_leaf_offset_and_ring():
    """Unit contract of the slot cache writer: seq leaves land at
    (offset + position) % S_cache — identity for full-size caches, tail
    kept and wrap-aligned for ring (sliding-window) caches — and state
    leaves are overwritten wholesale."""
    from repro.models.transformer import _write_slot_leaf

    src = jnp.arange(2 * 1 * 4 * 3, dtype=jnp.float32).reshape(2, 1, 4, 3)
    # full-size cache, nonzero offset: rows offset..offset+3
    dst = jnp.zeros((2, 3, 8, 3))
    out = np.asarray(_write_slot_leaf(dst, src, slot=1, offset=2,
                                      is_seq=True))
    np.testing.assert_array_equal(out[:, 1, 2:6], np.asarray(src[:, 0]))
    assert (out[:, 0] == 0).all() and (out[:, 2] == 0).all()
    assert (out[:, 1, :2] == 0).all() and (out[:, 1, 6:] == 0).all()

    # ring cache (S_cache=4) with a 6-long contribution at offset 0: the
    # tail (absolute positions 2..5) lands at rows 2,3,0,1
    src6 = jnp.arange(2 * 1 * 6 * 3, dtype=jnp.float32).reshape(2, 1, 6, 3)
    ring = jnp.full((2, 2, 4, 3), -1.0)
    out = np.asarray(_write_slot_leaf(ring, src6, slot=0, offset=0,
                                      is_seq=True))
    np.testing.assert_array_equal(out[:, 0, 2], np.asarray(src6[:, 0, 2]))
    np.testing.assert_array_equal(out[:, 0, 3], np.asarray(src6[:, 0, 3]))
    np.testing.assert_array_equal(out[:, 0, 0], np.asarray(src6[:, 0, 4]))
    np.testing.assert_array_equal(out[:, 0, 1], np.asarray(src6[:, 0, 5]))
    assert (out[:, 1] == -1.0).all()  # other slot untouched

    # seq leaf whose contribution exactly fills the cache still honors the
    # offset (rotation) — the case a shape-based state/seq test would
    # silently misplace
    full = jnp.zeros((2, 2, 4, 3))
    out = np.asarray(_write_slot_leaf(full, src, slot=0, offset=1,
                                      is_seq=True))
    np.testing.assert_array_equal(out[:, 0, 1:], np.asarray(src[:, 0, :3]))
    np.testing.assert_array_equal(out[:, 0, 0], np.asarray(src[:, 0, 3]))

    # state leaf (no extra seq axis in dst): wholesale overwrite at slot
    state = jnp.zeros((2, 3, 4, 3))
    out = np.asarray(_write_slot_leaf(state, src, slot=2, offset=0,
                                      is_seq=False))
    np.testing.assert_array_equal(out[:, 2], np.asarray(src[:, 0]))
    assert (out[:, :2] == 0).all()

    # the structural classifier distinguishes seq from state leaves
    from repro.configs import get_smoke
    from repro.models.transformer import _seq_leaf_kinds

    kinds = _seq_leaf_kinds(get_smoke("hymba-1.5b"), 0)
    flat = jax.tree_util.tree_flatten_with_path(kinds)[0]
    by_name = {path[-1].key: v for path, v in flat}
    assert by_name["k"] is True and by_name["v"] is True
    assert by_name["conv"] is False and by_name["ssm"] is False


def test_engine_ring_cache_window_model():
    """alt_local_global (ring local caches): the engine matches the
    one-shot loop when the ring alignment assumption holds, and matches
    the from-scratch parallel forward even when the prompt is longer than
    the window (where slot admission must wrap-align its writes)."""
    cfg = dataclasses.replace(get_smoke("gemma2-9b"), dtype="float32")
    params = init_lm(jax.random.PRNGKey(1), cfg)
    W = cfg.local_window

    # prompt shorter than the window: plain equivalence vs one-shot
    prompt = make_prompt(W - 4, seed=21, vocab=cfg.vocab)
    want = oneshot_greedy(params, cfg, prompt, gen_len=4)
    eng = ServeEngine(params, cfg, max_slots=2, max_seq_len=W + 4)
    outs = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=4)])
    assert outs[0].tokens == want

    # prompt 1.5x the window: ground truth is greedy re-decode with the
    # full parallel forward (no cache at all)
    from repro.models import forward, logits_of

    long_prompt = make_prompt(W + W // 2, seed=22, vocab=cfg.vocab)
    G = 3
    seq = list(long_prompt)
    want = []
    for _ in range(G):
        h, _ = forward(params, cfg, jnp.asarray(seq, jnp.int32)[None],
                       remat="none")
        tok = int(jnp.argmax(logits_of(params, cfg, h[:, -1:])[:, 0], -1)[0])
        want.append(tok)
        seq.append(tok)
    eng = ServeEngine(params, cfg, max_slots=2,
                      max_seq_len=len(long_prompt) + G)
    outs = eng.run([Request(uid=0, prompt=long_prompt, max_new_tokens=G)])
    assert outs[0].tokens == want


# ---------------------------------------------------------------------------
# paged engine: admission under page pressure, preemption, rejection
# ---------------------------------------------------------------------------


def test_out_of_pages_admission_queues_not_corrupts(setup):
    """A request that cannot get pages at admission is deferred (returned
    to the queue head) and served later — the requests already decoding in
    live slots produce exactly their unconstrained outputs."""
    cfg, params = setup
    reqs = lambda: [  # noqa: E731 — the slot-isolation trace, reused
        Request(uid=0, prompt=make_prompt(10, seed=3, vocab=cfg.vocab),
                max_new_tokens=5),
        Request(uid=10, prompt=make_prompt(12, seed=100, vocab=cfg.vocab),
                max_new_tokens=7),
        Request(uid=11, prompt=make_prompt(11, seed=101, vocab=cfg.vocab),
                max_new_tokens=7),
        Request(uid=12, prompt=make_prompt(13, seed=102, vocab=cfg.vocab),
                max_new_tokens=7),
    ]
    want = [(o.uid, o.tokens) for o in
            ServeEngine(params, cfg, max_slots=4, max_seq_len=20).run(reqs())]
    # 10 pages of 4 tokens: two ~4-page requests fit, the rest must defer
    eng = ServeEngine(params, cfg, max_slots=4, max_seq_len=20,
                      paged=True, page_size=4, num_pages=10,
                      prefix_sharing=False)
    got = [(o.uid, o.tokens) for o in eng.run(reqs())]
    assert got == want
    assert eng.stats["deferred_admissions"] > 0
    assert eng.stats["rejected"] == 0
    assert eng.kv.alloc.pages_in_use() == 0  # fully drained


def test_mid_stream_eviction_under_paging(setup):
    """Decode-time page exhaustion (prompts fit, growth does not) preempts
    the youngest slot, whose request is re-served from scratch — outputs
    still match the slot engine exactly."""
    cfg, params = setup
    reqs = lambda: [  # noqa: E731
        Request(uid=i, prompt=make_prompt(7 + i, seed=200 + i,
                                          vocab=cfg.vocab),
                max_new_tokens=9) for i in range(3)
    ]
    want = [(o.uid, o.tokens) for o in
            ServeEngine(params, cfg, max_slots=3, max_seq_len=20,
                        decode_chunk=4).run(reqs())]
    # 8 pages * 3 tokens = 24 token-rows: three 7-9 token prompts admit,
    # but 9 generated tokens each cannot all fit -> mid-stream preemption
    eng = ServeEngine(params, cfg, max_slots=3, max_seq_len=21,
                      decode_chunk=4, paged=True, page_size=3, num_pages=8,
                      prefix_sharing=False)
    got = [(o.uid, o.tokens) for o in eng.run(reqs())]
    assert got == want
    assert eng.stats["preemptions"] > 0
    assert eng.kv.alloc.pages_in_use() == 0


def test_too_long_prompt_rejected_not_fatal(setup):
    """Regression for the admission assert: an over-capacity prompt is
    rejected with finish_reason='rejected' while the serve loop keeps
    running and every well-formed request completes normally — for both
    cache backends."""
    cfg, params = setup
    for kw in ({}, {"paged": True, "page_size": 4}):
        eng = ServeEngine(params, cfg, max_slots=2, max_seq_len=12, **kw)
        outs = eng.run([
            Request(uid=0, prompt=make_prompt(6, seed=1, vocab=cfg.vocab),
                    max_new_tokens=3),
            Request(uid=1, prompt=make_prompt(30, seed=2, vocab=cfg.vocab),
                    max_new_tokens=3),
            Request(uid=2, prompt=make_prompt(7, seed=3, vocab=cfg.vocab),
                    max_new_tokens=3),
        ])
        by_uid = {o.uid: o for o in outs}
        assert by_uid[1].finish_reason == "rejected"
        assert by_uid[1].tokens == []
        assert len(by_uid[0].tokens) == len(by_uid[2].tokens) == 3
        met = eng.metrics()
        assert met.num_rejected == 1 and met.num_requests == 2
        assert np.isfinite(met.ttft_p50)


def test_frozen_clock_does_not_hang():
    """An injected clock that never advances must not hang run(): the
    engine warps virtual time to the next arrival instead of sleeping
    forever."""
    cfg = dataclasses.replace(get_smoke("bert-base-sten"), dtype="float32")
    params = init_lm(KEY, cfg)
    eng = ServeEngine(params, cfg, max_slots=1, max_seq_len=10,
                      clock=lambda: 0.0)
    outs = eng.run([Request(uid=0, prompt=make_prompt(6, seed=1,
                                                      vocab=cfg.vocab),
                            max_new_tokens=2, arrival_time=5.0)])
    assert len(outs) == 1 and len(outs[0].tokens) == 2
