"""Tests of the StarCoder2 serving driver (``bench/lib/serve_starcoder2.py``)
on the CPU, on a tiny cell of its kind with the window biting.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_starcoder2_cell.py

A run through ``bench/run.py`` with the chip check skipped comes out
correct; it comes out not correct with the served tokens altered; the fp8
control is judged not correct by the same rule; and the serving weights
(n:m:g FFN weights beside dense biases) convert without loss.
"""

from __future__ import annotations

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import reference_starcoder2 as reference
from bench.lib import registry, serve_starcoder2, traffic, weights
from bench.tests import tiny

CONFIG = dict(
    json.loads((tiny.REPO / "bench" / "configs" /
                "starcoder2-15b.json").read_text()),
    name="tiny-starcoder2", hidden_size=256, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=512,
    vocab_size=512, sliding_window=8)

CELL = {
    "kind": "serve_starcoder2", "why": "test",
    "engine": {"max_slots": 4, "max_seq_len": 64, "page_size": 16,
               "decode_chunk": 4},
    "arrivals": {"process": "poisson", "rate_hz": 4.0, "same_tail_s": 1.0},
    "prompt_len": {"dist": "lognormal", "mean": 14.0, "std": 6.0,
                   "buckets": [8, 16, 32]},
    "output_len": {"dist": "lognormal", "mean": 9.0, "std": 4.0, "min": 4},
    "check": {"min_tokens": 1000, "logit_gap_limit": 0.05},
}

NAME = "tiny-starcoder2.completion"


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "false")
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture
def root(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(tiny.REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(tiny.REPO / "src")
    spec = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny-starcoder2.json").write_text(
        json.dumps(CONFIG))
    spec["configs"].append({"name": CONFIG["name"], "source": "test",
                            "file": "bench/configs/tiny-starcoder2.json",
                            "reduced": [], "why": "test"})
    (root / "bench" / "workloads" / f"{NAME}.json").write_text(
        json.dumps(CELL))
    spec["workloads"].append({"name": NAME, "config": CONFIG["name"],
                              "traffic": "completion", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "starcoder2-15b.completion" in m.get("workloads", ()):
            m["workloads"].append(NAME)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _run(root, capsys):
    from bench import run as br

    rc = br.main(["--workload", NAME, "--seed", "3000000021",
                  "--seconds", "2", "--trace", "0"],
                 require_tpu=False, root=root)
    assert rc == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_cell_run_is_correct(root, capsys):
    res, err = _run(root, capsys)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    expected = {m["name"] for m in
                registry.Benchmark(root).metrics_for(NAME, False)}
    assert {"setup_s", "output_tok_s", "ttft_p95_ms"} <= expected
    assert set(res["metrics"]) == expected
    # prompts of 16 and 32 tokens behind a window of 8: the window bites
    masked = [int(line.split()[1]) for line in err.splitlines()
              if line.startswith("attn_window_masked_rows ")]
    assert masked and masked[0] > 0


def test_cell_fault_token_altered(root, capsys, monkeypatch):
    from repro.serve import engine

    orig = engine._jit_paged_decode_chunk.__wrapped__

    def altered(cfg, page_size, num_pages, n_steps):
        fn = orig(cfg, page_size, num_pages, n_steps)

        def chunk(*a):
            toks, pool = fn(*a)
            return (toks + 1) % cfg.vocab, pool

        return chunk

    monkeypatch.setattr(engine, "_jit_paged_decode_chunk", altered)
    assert _run(root, capsys)[0]["correct"] is False


def test_cell_control_is_not_correct(root):
    from bench.lib import serve

    bench = registry.Benchmark(root)
    wl, cfg = bench.workload(NAME), bench.config(CONFIG["name"])
    engine, clock = serve_starcoder2.make_engine(
        serve_starcoder2.serving_params(cfg, 9), wl, cfg)
    plan = traffic.requests_in_window(wl, 2.0, 9, cfg["vocab_size"])
    _, recs = serve.serve_plan(engine, clock, plan)
    correct, checks, control_correct = serve_starcoder2.compare(
        wl, cfg, 9, recs, control=True)
    assert correct is True and control_correct is False
    assert checks["control_logit_gap"]["value"] > \
        checks["control_logit_gap"]["limit"] > \
        checks["served_logit_gap"]["value"]


def test_biased_nmg_conversion_is_lossless():
    """The serving tree holds the drawn layer exactly: n:m:g FFN weights
    decompress to the dense draw, and every bias and norm parameter stays
    a dense leaf beside them."""
    from repro.core.layouts import GroupedNMTensor

    layout, dtype = CONFIG["serve_layout"], jnp.bfloat16
    params = serve_starcoder2.serving_params(CONFIG, 5)
    key = weights.seed_key(5)
    for i in range(CONFIG["num_hidden_layers"]):
        dense = reference.make_layer(weights.layer_key(key, i), CONFIG,
                                     layout, dtype)
        got = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        for name in ("wi", "wo"):
            w = got["mlp"][name]
            assert isinstance(w, GroupedNMTensor)
            np.testing.assert_array_equal(
                np.asarray(w.to_dense(), np.float32),
                np.asarray(dense["mlp"][name], np.float32))
            assert (np.asarray(dense["mlp"][name], np.float32) != 0
                    ).mean() == pytest.approx(0.25)
        del got["mlp"]["wi"], got["mlp"]["wo"]
        del dense["mlp"]["wi"], dense["mlp"]["wo"]
        assert jax.tree_util.tree_structure(got) == \
            jax.tree_util.tree_structure(dense)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(dense)):
            assert np.any(np.asarray(b, np.float32) != 0)
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
