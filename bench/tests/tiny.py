"""A tiny copy of the benchmark for tests on the CPU: the real files, plus
one small configuration and serving cell added by name."""

from __future__ import annotations

import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "name": "tiny", "source": "test", "hidden_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 512,
    "vocab_size": 512, "max_position_embeddings": 128,
    "hidden_act": "gelu_tanh", "rope_theta": 10000.0,
    "torch_dtype": "bfloat16",
    "serve_layout": {"kind": "nmg", "n": 1, "m": 4, "g": 16, "gr": 64,
                     "targets": ["mlp.wi", "mlp.wo"]},
    "reduced": [],
}

TINY_CHAT = {
    "kind": "serve", "why": "test",
    "engine": {"max_slots": 4, "max_seq_len": 64, "page_size": 16,
               "decode_chunk": 4},
    "arrivals": {"process": "poisson", "rate_hz": 4.0, "same_tail_s": 1.0},
    "prompt_len": {"dist": "lognormal", "mean": 10.0, "std": 6.0,
                   "buckets": [8, 16]},
    "output_len": {"dist": "lognormal", "mean": 9.0, "std": 4.0, "min": 4},
    "check": {"min_tokens": 1000, "logit_gap_limit": 0.05},
}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-like tree under ``tmp``: ``BENCHMARK.json``, a copy of
    ``bench/`` and the system's ``src/``, with the tiny parts added."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(TINY_CONFIG))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    (root / "bench" / "workloads" / "tiny.chat.json").write_text(
        json.dumps(TINY_CHAT))
    spec["workloads"].append({"name": "tiny.chat", "config": "tiny",
                              "traffic": "chat", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [
                w["name"].replace("bert-base-sten", "tiny")
                for w in spec["workloads"]
                if w["name"] in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
