"""repro.obs tests: flight-recorder ring-buffer properties, Chrome trace
schema/nesting validation, registry semantics (in-place reset, event
emission, the muted bulk-restore path), exporters, the nan-safe metrics
edge cases, and the engine-level guarantees the observability PR ships
on: tracing changes no tokens, and a warm engine records no new JIT
traces with the recorder on.  Spans also reach a running profiler's host
plane (one span API, two timelines), and the serving programs carry the
named scopes a device trace is reduced by.
"""

import collections
import dataclasses
import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import init_lm
from repro.obs import trace as obs
from repro.obs.export import (
    phase_breakdown,
    prometheus_text,
    to_chrome_trace,
    to_jsonl,
    validate_chrome_trace,
)
from repro.obs.registry import (
    REGISTRY,
    CounterFamily,
    MirroredCounters,
    snapshot_diff,
)
from repro.serve import Request, ServeEngine, summarize, trace_events
from repro.statutil import fmt, pct

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# flight recorder: bounded ring buffer
# ---------------------------------------------------------------------------


def test_disabled_mode_records_nothing():
    """Off by default (the conftest fixture pins that): events vanish and
    span() hands back the shared no-op singleton — the zero-allocation
    fast path."""
    assert not obs.enabled()
    obs.event("x", "engine", k=1)
    with obs.span("s", "engine"):
        pass
    obs.complete("c", 0.0, 1.0)
    assert obs.records() == [] and obs.dropped() == 0
    assert obs.span("a") is obs.span("b")


def test_ring_buffer_bounded_overwrites_oldest():
    obs.enable(capacity=8)
    for i in range(20):
        obs.event(f"e{i}", "engine", i=i)
    recs = obs.records()
    assert len(recs) == 8 == obs.capacity()
    assert [r[1] for r in recs] == [f"e{i}" for i in range(12, 20)]
    assert obs.dropped() == 12


def test_span_records_complete_event_with_error_attr():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("boom", "engine", k=3):
            raise ValueError("x")
    (ph, name, track, ts, dur, attrs), = obs.records()
    assert (ph, name, track) == ("X", "boom", "engine")
    assert ts >= 0 and dur >= 0
    assert attrs == {"k": 3, "error": "ValueError"}


def test_disable_mid_span_drops_the_record():
    obs.enable()
    with obs.span("torn", "engine"):
        obs.disable()
    assert obs.records() == []


def test_reenable_keeps_epoch_timestamps_monotonic():
    """A disable/enable cycle with held records (the fig11 overhead probe
    toggling tracing mid-run) must stay on one monotonic timeline."""
    obs.enable()
    obs.event("a", "engine")
    obs.disable()
    obs.enable()
    obs.event("b", "engine")
    ts = [r[3] for r in obs.records()]
    assert len(ts) == 2 and ts == sorted(ts)


# ---------------------------------------------------------------------------
# Chrome trace export + schema validation
# ---------------------------------------------------------------------------


def _chrome_doc():
    obs.enable()
    with obs.span("outer", "engine", a=1):
        with obs.span("inner", "engine"):
            pass
    obs.event("mark", "controller", tier="dense")
    return to_chrome_trace(obs.records(), registry_snapshot={"x": 1},
                           dropped=3)


def test_chrome_trace_schema():
    doc = _chrome_doc()
    assert validate_chrome_trace(doc) == []
    assert doc["metadata"] == {"tool": "repro.obs", "dropped_records": 3,
                               "registry": {"x": 1}}
    for ev in doc["traceEvents"]:
        assert {"ph", "ts", "pid", "tid", "name"} <= set(ev)
        assert ev["pid"] == 1
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["args"]["name"] for e in meta} >= {"engine", "controller"}
    # tracks map to distinct thread rows
    by_name = {e["name"]: e for e in doc["traceEvents"] if e["ph"] != "M"}
    assert by_name["outer"]["tid"] != by_name["mark"]["tid"]
    assert by_name["mark"]["s"] == "t"
    json.dumps(doc)  # JSON-serializable end to end


def test_chrome_trace_spans_nest_properly():
    doc = _chrome_doc()
    spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    o, i = spans["outer"], spans["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"]


def test_validator_flags_partial_overlap_and_missing_fields():
    bad = to_chrome_trace([("X", "a", "engine", 0, 100, None),
                           ("X", "b", "engine", 50, 100, None)])
    assert any("partially overlaps" in p for p in validate_chrome_trace(bad))
    assert validate_chrome_trace({"traceEvents": [{"ph": "X", "ts": 0}]})
    assert validate_chrome_trace({}) == ["traceEvents missing or not a list"]


def test_jsonl_and_phase_breakdown():
    obs.enable()
    with obs.span("work", "engine"):
        pass
    obs.event("mark", "engine")
    lines = [json.loads(ln) for ln in to_jsonl(obs.records()).splitlines()]
    assert [ln["name"] for ln in lines] == ["work", "mark"]
    assert "dur_us" in lines[0] and "dur_us" not in lines[1]
    pb = phase_breakdown(obs.records())
    assert list(pb) == ["work"] and pb["work"]["count"] == 1


# ---------------------------------------------------------------------------
# telemetry registry
# ---------------------------------------------------------------------------


def test_registry_constructors_idempotent_and_typed():
    c = REGISTRY.counter("obs_test_c")
    assert REGISTRY.counter("obs_test_c") is c
    with pytest.raises(TypeError):
        REGISTRY.gauge("obs_test_c")


def test_family_emits_timeline_events_only_on_increase():
    fam = REGISTRY.family("obs_test_fam", trace_as="probe", track="registry")
    fam[("a", "b")] += 1  # recorder off: counted, not recorded
    obs.enable()
    fam[("a", "b")] += 2
    recs = obs.records()
    assert len(recs) == 1
    assert recs[0][1] == "probe" and recs[0][5] == {"key": "a/b", "n": 2}
    # bulk restore (predict_route's snapshot/restore dance) stays silent
    snap = fam.copy()
    assert type(snap) is not CounterFamily
    fam.clear()
    fam.update(snap)
    assert len(obs.records()) == 1
    assert fam[("a", "b")] == 3


def test_mirrored_counters_reads_like_a_dict():
    fam = REGISTRY.family("obs_test_mirror")
    stats = MirroredCounters({"served": 0, "label": "x"}, fam)
    stats["served"] += 2
    stats["served"] += 1
    stats["label"] = "y"  # non-numeric writes pass through unmirrored
    assert dict(stats) == {"served": 3, "label": "y"}
    assert fam["served"] == 3 and "label" not in fam


def test_histogram_snapshot_cumulative_and_prometheus():
    h = REGISTRY.histogram("obs_test_hist", buckets=(0.001, 0.01))
    for v in (0.0005, 0.005, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["buckets"] == {"0.001": 1, "0.01": 2, "+Inf": 3}
    assert snap["count"] == 3
    txt = prometheus_text(REGISTRY.snapshot())
    assert 'repro_obs_test_hist_bucket{le="0.001"} 1' in txt
    assert "repro_obs_test_hist_count 3" in txt


def test_registry_reset_in_place_and_snapshot_diff():
    fam = REGISTRY.family("obs_test_diff")
    before = REGISTRY.snapshot()
    fam["k"] += 2
    REGISTRY.gauge("obs_test_g").set(1.5)
    d = snapshot_diff(before, REGISTRY.snapshot())
    assert d["obs_test_diff"] == {"k": 2} and d["obs_test_g"] == 1.5
    REGISTRY.reset()
    assert REGISTRY.family("obs_test_diff") is fam and len(fam) == 0


# ---------------------------------------------------------------------------
# metrics edge cases (satellite: nan-safe summarize/report)
# ---------------------------------------------------------------------------


def test_summarize_zero_wall_time_is_nan_not_inf():
    met = summarize([], 0.0, label="empty")
    assert met.num_requests == 0
    assert np.isnan(met.throughput_tok_s)
    assert np.isnan(met.ttft_p50) and np.isnan(met.tok_latency_p99)
    # and report() renders every nan as "--" instead of raising
    rep = met.report()
    assert "--" in rep and "nan" not in rep


def test_statutil_helpers():
    assert np.isnan(pct([], 99))
    assert pct([1.0, 2.0, 3.0], 50) == 2.0
    assert fmt(float("nan")) == "--"
    assert fmt(0.0123, 1e3, 2) == "12.30"


# ---------------------------------------------------------------------------
# engine-level guarantees (token equivalence, no retrace with recorder on)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke("bert-base-sten"), dtype="float32")
    params = init_lm(KEY, cfg)
    yield cfg, params
    from repro.serve import cache as _cache, engine as _engine
    for mod in (_cache, _engine):
        for fn in vars(mod).values():
            clear = getattr(fn, "cache_clear", None)
            if clear is not None:
                clear()
    jax.clear_caches()


def _reqs(cfg, n=3, plen=8, gen=6):
    return [Request(uid=u, max_new_tokens=gen,
                    prompt=np.asarray(jax.random.randint(
                        jax.random.PRNGKey(u), (plen,), 0, cfg.vocab,
                        jnp.int32)))
            for u in range(n)]


def test_tracing_changes_no_tokens_and_emits_lifecycle_spans(setup):
    cfg, params = setup
    ekw = dict(max_slots=2, max_seq_len=24, decode_chunk=4)
    off = ServeEngine(params, cfg, **ekw).run(_reqs(cfg))
    assert obs.records() == []  # recorder off: the run left no trace
    obs.enable()
    on = ServeEngine(params, cfg, **ekw).run(_reqs(cfg))
    assert [o.tokens for o in on] == [o.tokens for o in off]
    names = {r[1] for r in obs.records()}
    assert {"queued", "finish", "engine.step", "engine.admit",
            "engine.decode", "engine.decode.prepare",
            "engine.decode.fetch", "engine.emit"} <= names
    # the retroactive per-request decode rows are gone: the live engine
    # spans carry the call's counters instead
    assert not names & {"prefill", "decode_call", "decode_chunk",
                        "decode_step"}
    decodes = [r for r in obs.records() if r[1] == "engine.decode"]
    assert all(r[5]["steps"] in (1, 4) and 1 <= r[5]["n_active"] <= 2
               for r in decodes)
    # every request got its own track row, and the export validates
    tracks = {r[2] for r in obs.records()}
    assert {f"req:{u}" for u in range(3)} <= tracks
    doc = to_chrome_trace(obs.records())
    assert validate_chrome_trace(doc) == []


def test_warm_engine_records_no_new_jit_traces_with_recorder_on(setup):
    """Recompile safety: with the flight recorder enabled, serving and
    tier switches on a warmed engine add no ``trace_events`` — tracing is
    host-side and must never perturb the JIT caches."""
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_slots=2, max_seq_len=24,
                      decode_chunk=4, tiers=["dense", "1:4:8-gr64"])
    eng.warm_tiers(prompt_lens=(8,))
    obs.enable()
    before = dict(trace_events())
    eng.run(_reqs(cfg))
    eng.set_tier(1)
    eng.run(_reqs(cfg))
    assert trace_events() == before
    assert not [r for r in obs.records() if r[1] == "jit_trace"]
    switches = [r for r in obs.records() if r[1] == "tier_switch"]
    assert switches and switches[-1][5]["tier_to"] == "1:4:8-gr64"


# ---------------------------------------------------------------------------
# one span API, two timelines; named scopes in the serving programs
# ---------------------------------------------------------------------------

PAGED = dict(max_slots=2, max_seq_len=32, decode_chunk=4, paged=True,
             page_size=8)


def _host_spans(log_dir) -> dict:
    """``{name: [(start_ns, end_ns, stats)]}`` of the host-plane events of
    the profile written under ``log_dir``."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    spans = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    spans[e.name].append((e.start_ns,
                                          e.start_ns + e.duration_ns,
                                          dict(e.stats)))
    return spans


def _within(inner, outers) -> bool:
    return any(o[0] <= inner[0] and inner[1] <= o[1] for o in outers)


def test_engine_spans_land_on_the_profiler_host_plane(setup, tmp_path,
                                                      monkeypatch):
    """With tracing on, the engine's live spans are profiler annotations
    too, nested as the loop runs: a step holds its admissions, its decode
    call (page preparation, then the blocking fetch) and the token
    bookkeeping; an admission ends after its first token was fetched and
    sampled, so it holds the device's prefill, not only its enqueue."""
    cfg, params = setup
    from repro.serve import engine as engine_mod

    sample = engine_mod.sample_token

    def marked(*a, **kw):
        with jax.profiler.TraceAnnotation("test.first_token"):
            return sample(*a, **kw)

    monkeypatch.setattr(engine_mod, "sample_token", marked)
    ServeEngine(params, cfg, **PAGED).run(_reqs(cfg))  # compile untraced
    obs.enable()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        ServeEngine(params, cfg, **PAGED).run(_reqs(cfg))
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    steps, admits = spans["engine.step"], spans["engine.admit"]
    decodes = spans["engine.decode"]
    assert len(admits) == 3 and decodes and spans["engine.emit"]
    assert all(_within(a, steps) for a in admits)
    assert all(_within(d, steps) for d in decodes)
    assert all(_within(e, steps) for e in spans["engine.emit"])
    for inner in ("engine.decode.prepare", "engine.decode.fetch"):
        assert len(spans[inner]) == len(decodes)
        assert all(_within(x, decodes) for x in spans[inner])
    assert all(d[2]["steps"] == 4 and d[2]["n_active"] in (1, 2)
               for d in decodes)
    assert sorted(d[2]["call"] for d in decodes) == list(range(len(decodes)))
    assert sorted(a[2]["uid"] for a in admits) == [0, 1, 2]
    assert all(_within(t, admits) for t in spans["test.first_token"])
    assert len(spans["test.first_token"]) == len(admits)


def test_disabled_spans_build_no_annotation_and_change_no_tokens(
        setup, monkeypatch):
    cfg, params = setup
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    assert obs.span("engine.step", queue=1) is obs.span("engine.decode")
    off = ServeEngine(params, cfg, **PAGED).run(_reqs(cfg))
    assert built == []
    obs.enable()
    on = ServeEngine(params, cfg, **PAGED).run(_reqs(cfg))
    assert [o.tokens for o in on] == [o.tokens for o in off]
    assert {"engine.step", "engine.admit", "engine.decode"} <= set(built)


def test_span_is_a_profiler_annotation_and_complete_is_not(monkeypatch):
    """Enabled, a span enters one ``TraceAnnotation`` carrying its attrs,
    including those set inside it; the retroactive ``complete`` writes the
    flight recorder only."""
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append((name, kw))
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    obs.enable()
    with obs.span("engine.decode", "engine", call=0) as sp:
        sp.set(steps=8, n_active=3)
    obs.complete("queued", 0.0, 1.0, "req:0")
    assert built == [("engine.decode", {"call": 0})]
    span, queued = obs.records()
    assert span[1] == "engine.decode"
    assert span[5] == {"call": 0, "steps": 8, "n_active": 3}
    assert queued[1] == "queued"


def _scopes(lowered) -> set:
    names = re.findall(r'op_name="([^"]*)"',
                       lowered.as_text("hlo", debug_info=True))
    return {part for n in names for part in n.split("/")}


def test_serving_programs_carry_named_scopes(setup):
    """The paged decode chunk (``jit_chunk``) and the paged prefill
    (``jit_run``) name what moves the cache and what computes, for a
    device trace's ``tf_op`` to be read by."""
    from repro.serve import cache as cache_mod, engine as engine_mod

    cfg, params = setup
    kv = cache_mod.PagedKVCache(cfg, 2, 32, page_size=8)
    chunk = engine_mod._jit_paged_decode_chunk(cfg, 8, kv.num_pages, 4)
    lowered = chunk.lower(params, jnp.zeros((2, 1), jnp.int32), kv.data,
                          kv.device_table(), jnp.zeros(2, jnp.int32))
    assert {"kv.view", "kv.commit", "kv.write", "decode.chunk",
            "decode.step", "decode.layers", "decode.layer",
            "attn.decode"} <= _scopes(lowered)
    prefill = cache_mod._jit_paged_prefill(cfg, 8, kv.num_pages)
    lowered = prefill.lower(params, jnp.zeros((1, 8), jnp.int32), kv.data,
                            jnp.asarray(kv.table[0]), jnp.int32(0),
                            jnp.int32(0))
    assert "kv.prefill_write" in _scopes(lowered)
