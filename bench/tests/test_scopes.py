"""Tests of the reduction by scope and engine span (``bench/lib/scopes.py``)
on recorded TPU traces, on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_scopes.py

``tiny_serve_scoped.xplane.pb.gz`` is a traced run of the tiny cell with the
engine spans on and the programs' named scopes; its facts were read by hand
with TensorFlow's own XPlane reader.  ``tiny_serve.xplane.pb.gz`` predates
both, as the parent program's traces do: there the reduction finds nothing
to read, and says so.
"""

from __future__ import annotations

import gzip
import json
import pathlib
import shutil

import pytest

from bench.lib import scopes, trace

DATA = pathlib.Path(__file__).parent / "data"


def _unpacked(tmp_path, name: str) -> str:
    path = tmp_path / name
    with gzip.open(DATA / f"{name}.gz") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.fixture
def scoped(tmp_path):
    facts = json.loads((DATA / "tiny_serve_scoped.facts.json").read_text())
    path = _unpacked(tmp_path, "tiny_serve_scoped.xplane.pb")
    return (scopes.reduce_scopes(path, "bench.serve", facts["window_s"]),
            trace.reduce_trace(path, "bench.serve", facts["window_s"]),
            facts)


def test_scope_of_a_name_stack():
    stack = ("jit(chunk)/decode.chunk/while/body/decode.step/decode.layers/"
             "while/body/decode.layer/kv.write/dynamic_update_slice:")
    assert scopes.scope_of(stack) == "kv.write"
    assert scopes.scope_of("jit(chunk)/decode.chunk/while:") == \
        "decode.chunk"
    assert scopes.scope_of(
        "jit(chunk)/decode.chunk/while/body/decode.step/decode.layers/while/"
        "body/decode.layer/repro.nmg_spmm_pallas[grid]/pallas_call:") == \
        "repro.nmg_spmm_pallas[grid]"
    assert scopes.scope_of("jit(run)/while/body/closed_call/add:") == \
        "unscoped"
    # no name stack: an op XLA added (a copy, a prefetch, an argument's
    # relayout)
    assert scopes.scope_of("") == "xla.inserted"
    assert scopes.scope_of("pool['k']:") == "xla.inserted"


def test_scoped_trace_reads_as_read_by_hand(scoped):
    s, tr, facts = scoped
    assert s.chips == 1
    assert s.program_calls["jit_chunk"] == facts["jit_chunk_calls"] == \
        tr.program_calls["jit_chunk"]
    assert s.program_calls["jit_run"] == facts["jit_run_calls"]
    assert len(s.spans("engine.decode")) == facts["engine_decode_spans"]
    assert len(s.spans("engine.admit")) == facts["engine_admit_spans"]
    assert sorted(k for p, k in s.scope_s if p == "jit_chunk") == \
        facts["jit_chunk_scopes"]
    chunk = sum(v for (p, _), v in s.scope_s.items() if p == "jit_chunk")
    for k in ("unscoped", "xla.inserted"):
        assert s.scope_s.get(("jit_chunk", k), 0.0) / chunk == \
            pytest.approx(facts[f"jit_chunk_{k}_share"], rel=1e-6, abs=1e-12)
    assert s.engine_idle == pytest.approx(facts["engine_idle"], rel=1e-6)
    # every idle gap is charged once: the charges sum to the window's idle
    assert sum(s.engine_idle.values()) == pytest.approx(
        tr.window_s - tr.busy_s, rel=1e-6)
    assert all(d.attrs["steps"] == facts["decode_chunk"]
               for d in s.spans("engine.decode"))


def test_metrics_read_as_read_by_hand(scoped):
    s, _, facts = scoped
    for name, value in (
            ("kv_move_ms_per_step",
             scopes.kv_move_ms_per_step(s, facts["decode_chunk"])),
            ("host_idle_ms_per_step", scopes.host_idle_ms_per_step(s)),
            ("admit_ms_p50", scopes.admit_ms_p50(s))):
        assert value == pytest.approx(facts[name], rel=1e-6), name
    b = scopes.breakdown(s)
    assert len(b["device_scopes"]) <= 10
    assert b["device_scopes"][0][1] == max(s.scope_s.values())
    assert {k for k, _ in b["engine_idle"]} == set(s.engine_idle)


def test_a_trace_without_scopes_or_spans_reads_nothing(tmp_path):
    """The parent program's trace: the reduction runs, charges all idle to
    ``engine.none`` and finds no scope of its own, and the metrics read
    None rather than raising."""
    path = _unpacked(tmp_path, "tiny_serve.xplane.pb")
    s = scopes.reduce_scopes(path, "bench.serve", 1.0)
    tr = trace.reduce_trace(path, "bench.serve", 1.0)
    assert s.program_calls == tr.program_calls
    assert set(s.engine_idle) == {"engine.none"}
    assert s.engine_idle["engine.none"] == pytest.approx(
        tr.window_s - tr.busy_s, rel=1e-6)
    assert {k for _, k in s.scope_s} <= {"unscoped", "xla.inserted",
                                          "repro.nmg_gemv_pallas"}
    assert s.engine_spans == []
    assert scopes.kv_move_ms_per_step(s, 4) is None
    assert scopes.host_idle_ms_per_step(s) is None
    assert scopes.admit_ms_p50(s) is None
