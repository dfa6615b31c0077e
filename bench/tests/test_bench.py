"""Tests of the benchmark itself, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

They cover the loader (new parts found by name), the required-work counts,
the trace reduction on a recorded TPU trace, the weights the benchmark
draws, the refusal to run without a chip, and the comparison: a run of the
tiny cell through the serving driver with the chip check skipped comes out
correct, comes out not correct with the timed path broken underneath, and
the fp8 control is judged not correct by the same rule.
"""

from __future__ import annotations

import gzip
import json
import pathlib
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import registry, trace, weights, work
from bench.tests import tiny

DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "false")
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture
def root(tmp_path):
    return tiny.make_root(tmp_path)


def _run(root, workload, capsys, seconds="2"):
    from bench import run as br

    rc = br.main(["--workload", workload, "--seed", "3000000017",
                  "--seconds", seconds, "--trace", "0"],
                 require_tpu=False, root=root)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# -- loader ---------------------------------------------------------------


def test_loader_finds_new_parts_by_name(root):
    (root / "bench" / "metrics" / "tiny_probe.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "tiny_probe", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "device", "moves": "setup_s",
                              "workloads": ["tiny.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = registry.Benchmark(root)
    assert bench.workload("tiny.chat")["kind"] == "serve"
    assert bench.workload("tiny.chat")["config"] == "tiny"
    assert bench.config("tiny")["hidden_size"] == 256
    names = [m["name"] for m in bench.metrics_for("tiny.chat", True)]
    assert "tiny_probe" in names
    assert bench.reader("tiny_probe")(None) == 42.0


def test_every_named_part_has_its_file():
    bench = registry.Benchmark(tiny.REPO)
    for w in bench.spec["workloads"]:
        assert bench.workload(w["name"])["kind"] == "serve"
        bench.config(w["config"])
    for m in bench.spec["end_to_end"] + bench.spec["per_layer"]:
        assert callable(bench.reader(m["name"]))


# -- required work --------------------------------------------------------


def test_nmg_work_by_hand():
    layout = {"n": 1, "m": 4, "g": 16, "gr": 64}
    flops, byts = work.nmg_matmul(2, 256, 64, layout)
    assert flops == 2 * 2 * 256 * 64 / 4
    # 64 columns x 64 blocks x 1 kept value x 2 bytes, one int32 block
    # index per block for the single group of 64 columns, bf16 in and out
    assert byts == 64 * 64 * 2 + 64 * 4 + (2 * 256 + 2 * 64) * 2
    peaks = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    t, bound = work.least_time(flops, byts, peaks)
    assert bound == "memory" and t == pytest.approx(byts / 1e9)
    layout2 = {"n": 2, "m": 4, "g": 8, "gr": 8}
    flops, byts = work.nmg_matmul(1, 96, 16, layout2)
    assert flops == 2 * 96 * 16 / 2
    assert byts == 16 * 24 * 2 * 2 + 2 * 24 * 4 + (96 + 16) * 2


def test_model_work_by_hand():
    cfg = tiny.TINY_CONFIG
    # per layer: q 256x256, k and v 256x128 each, o 256x256, ffn 2x256x512
    per_layer = 2 * (256 * 256 * 2 + 256 * 128 * 2 + 256 * 512 * 2)
    head = 2 * 256 * 512
    assert work.weight_flops_per_token(cfg) == 2 * per_layer + head
    dens = {"mlp.wi": 0.25, "mlp.wo": 0.25}
    sparse_layer = per_layer - 2 * 256 * 512 * 2 * 0.75
    assert work.weight_flops_per_token(cfg, dens) == 2 * sparse_layer + head
    # attention: 4 x context x heads x head_dim per layer
    assert work.attention_flops(cfg, 10) == 2 * 4 * 10 * 4 * 64
    S = 3
    assert work.prefill_flops(cfg, S) == (
        S * 2 * per_layer + head + 2 * 4 * 4 * 64 * (1 + 2 + 3))


# -- trace reduction -------------------------------------------------------


def _recorded_trace(tmp_path):
    path = tmp_path / "tiny_serve.xplane.pb"
    with gzip.open(DATA / "tiny_serve.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_trace_reduction_on_a_recorded_trace(tmp_path):
    facts = json.loads((DATA / "tiny_serve.facts.json").read_text())
    s = trace.reduce_trace(_recorded_trace(tmp_path), "bench.serve",
                           facts["window_s"])
    assert s.chips == 1
    assert s.window_s == pytest.approx(facts["window_s"])
    assert 0 < s.busy_s < s.window_s
    assert s.program_calls["jit_chunk"] == facts["jit_chunk_calls"]
    assert s.program_calls["jit_run"] == facts["jit_run_calls"]
    kernels = {k for k, _ in s.kernel_s}
    assert kernels == set(facts["kernels"])
    assert all(prog in ("jit_chunk", "jit_run")
               for _, prog in s.kernel_s)
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert len(s.top_ops) <= 10 and len(s.idle_gaps) <= 10
    assert all(name.startswith("bench.") for name, _ in s.idle_gaps)


def test_trace_names():
    assert trace.module_name("jit_chunk(16376077838839813834)") == \
        "jit_chunk"
    name = ("%nmg_spmm_pallas.12 = f32[48,32,64]{2,1,0} custom-call("
            "s32[48,1,192] %x)")
    assert trace.op_name(name) == "nmg_spmm_pallas"
    assert trace.is_kernel(name)
    assert trace.op_name("%copy.56 = bf16[32] copy(bf16[32] %p)") == "copy"
    assert not trace.is_kernel("%copy.56 = bf16[32] copy(bf16[32] %p)")


# -- traffic ---------------------------------------------------------------


def test_seeds_share_the_work_and_the_tail():
    from bench.lib import traffic

    wl = json.loads((tiny.REPO / "bench" / "workloads" /
                     "bert-base-sten.chat.json").read_text())
    seconds, rate = 40.0, wl["arrivals"]["rate_hz"]
    a, b = (traffic.requests_in_window(wl, seconds, s, 1000)
            for s in (5, 2 ** 31 + 77))
    n_tail = int(np.ceil(rate * wl["arrivals"]["same_tail_s"]))
    assert len(a) == len(b) == round(rate * seconds) > n_tail

    def sizes(plan):
        return [(p.prompt.size, p.max_new_tokens) for p in plan]

    assert sorted(sizes(a)) == sorted(sizes(b))
    assert sizes(a)[-n_tail:] == sizes(b)[-n_tail:]
    assert sizes(a)[:-n_tail] != sizes(b)[:-n_tail]
    np.testing.assert_allclose([p.due_s for p in a[-n_tail:]],
                               [p.due_s for p in b[-n_tail:]], atol=1e-9)
    assert a[-n_tail].due_s == pytest.approx(
        seconds - wl["arrivals"]["same_tail_s"], abs=1.0)
    assert max(p.due_s for p in a) < seconds
    assert not all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))


# -- weights ---------------------------------------------------------------


def test_nmg_weights_convert_without_loss():
    from repro.serve.engine import sparsify_for_serving

    cfg = tiny.TINY_CONFIG
    layout = cfg["serve_layout"]
    p = weights.make_params(weights.seed_key(5), cfg, layout, jnp.bfloat16)
    sp = sparsify_for_serving(p, 1, 4, 16, gr=64)
    for name in ("wi", "wo"):
        w = np.asarray(p["layers"]["mlp"][name], np.float32)
        got = np.asarray(jax.vmap(lambda t: t.to_dense())(
            sp["layers"]["mlp"][name]), np.float32)
        np.testing.assert_array_equal(got, w)
        assert (w != 0).mean() == pytest.approx(0.25)


def test_layers_drawn_alike_stacked_and_alone():
    cfg = tiny.TINY_CONFIG
    key = weights.seed_key(2 ** 31 + 12345)
    p = weights.make_params(key, cfg, cfg["serve_layout"], jnp.bfloat16)
    one = weights.make_layer(weights.layer_key(key, 1), cfg,
                             cfg["serve_layout"], jnp.bfloat16)
    for a, b in zip(jax.tree_util.tree_leaves(one),
                    jax.tree_util.tree_leaves(
                        jax.tree_util.tree_map(lambda x: x[1],
                                               p["layers"]))):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# -- the chip check --------------------------------------------------------


def test_without_a_chip_the_run_refuses(root, capsys):
    from bench import run as br

    rc = br.main(["--workload", "tiny.chat", "--seed", "1", "--seconds",
                  "1", "--trace", "0"], root=root)
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "not a TPU" in out.err


# -- the comparison ---------------------------------------------------------


def test_serving_run_is_correct(root, capsys):
    res = _run(root, "tiny.chat", capsys)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    expected = {m["name"] for m in
                registry.Benchmark(root).metrics_for("tiny.chat", False)}
    assert "setup_s" in expected and set(res["metrics"]) == expected
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_serving_fault_token_altered(root, capsys, monkeypatch):
    from repro.serve import engine

    orig = engine._jit_paged_decode_chunk.__wrapped__

    def altered(cfg, page_size, num_pages, n_steps):
        fn = orig(cfg, page_size, num_pages, n_steps)

        def chunk(*a):
            toks, pool = fn(*a)
            return (toks + 1) % cfg.vocab, pool

        return chunk

    monkeypatch.setattr(engine, "_jit_paged_decode_chunk", altered)
    assert _run(root, "tiny.chat", capsys)["correct"] is False


def test_serving_fault_state_unchanged(root, capsys, monkeypatch):
    from repro.serve import engine

    orig = engine._jit_paged_decode_chunk.__wrapped__

    def frozen(cfg, page_size, num_pages, n_steps):
        fn = orig(cfg, page_size, num_pages, n_steps)

        def chunk(p, tok, pool, table, pos):
            keep = jax.tree_util.tree_map(jnp.copy, pool)
            toks, _ = fn(p, tok, pool, table, pos)
            return toks, keep

        return chunk

    monkeypatch.setattr(engine, "_jit_paged_decode_chunk", frozen)
    assert _run(root, "tiny.chat", capsys)["correct"] is False


def test_serving_control_is_not_correct(root):
    from bench.lib import serve, traffic

    bench = registry.Benchmark(root)
    wl = bench.workload("tiny.chat")
    cfg = bench.config("tiny")
    engine, clock = serve.make_engine(serve.serving_params(cfg, 9), wl, cfg)
    plan = traffic.requests_in_window(wl, 2.0, 9, cfg["vocab_size"])
    _, recs = serve.serve_plan(engine, clock, plan)
    correct, checks, control_correct = serve.compare(wl, cfg, 9, recs,
                                                     control=True)
    assert correct is True and control_correct is False
    assert checks["control_logit_gap"]["value"] > \
        checks["control_logit_gap"]["limit"] > \
        checks["served_logit_gap"]["value"]
