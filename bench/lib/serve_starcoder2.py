"""The serving driver for StarCoder2's block: ``serve.py``'s open-loop run
through ``ServeEngine``, with this configuration's model, weights and
reference.

``serve.py`` builds bert-base-sten's block (RMS norm, no biases, scaled
embeddings); this driver builds the block the configuration file states
(LayerNorm with bias, biased projections, a sliding window on every layer,
unscaled embeddings) and compares the served tokens with
``reference_starcoder2``.  Everything else is ``serve.py``'s: the window,
the request records, the sample of requests the comparison reads, and the
``Run`` every serving reader takes.

Set-up draws each layer from the seed and converts it to the serving
layout before drawing the next, so the dense model (7.3 GB at 8 layers of
published widths) is never held beside the sparse one.  The comparison
runs each sampled request at its own length, rounded up to whole blocks of
the reference's attention (one compile per rounded length), not at the
engine's capacity.
"""

from __future__ import annotations

import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import reference_starcoder2 as reference
from bench.lib import serve, traffic, weights
from bench.lib.record import Run

#: the host span whose start opens the measured window in a trace
WINDOW_SPAN = serve.WINDOW_SPAN


def model_config(cfg: dict):
    """The system's ``ModelConfig`` for a StarCoder2 configuration file."""
    from repro.models.common import ModelConfig

    d = weights.model_dims(cfg)
    for key, want in (("hidden_act", "gelu_pytorch_tanh"),
                      ("norm_type", "layer_norm"), ("norm_epsilon", 1e-5),
                      ("mlp_type", "default")):
        if cfg[key] != want:
            raise ValueError(f"{key} {cfg[key]!r}: the system runs {want!r}")
    return ModelConfig(
        name=cfg["name"], vocab=d["V"], d_model=d["D"], n_layers=d["L"],
        n_heads=d["H"], n_kv_heads=d["KV"], head_dim=d["hd"], d_ff=d["F"],
        attn_type="gqa", qkv_bias=cfg["use_bias"], proj_bias=cfg["use_bias"],
        norm_type="layer", local_window=cfg["sliding_window"],
        layer_pattern="local", act="gelu", gated_mlp=False,
        rope_theta=float(cfg["rope_theta"]), embed_scale=False,
        tie_embeddings=False, dtype=cfg["torch_dtype"])


def serving_params(cfg: dict, seed: int):
    """The configuration's weights from the seed, in its serving layout:
    the outer weights in one jitted call, then each layer drawn and
    converted into its slot of the stacked sparse tree by one jitted call
    (the stack donated)."""
    from repro.serve.engine import sparsify_for_serving

    layout, dtype = cfg["serve_layout"], jnp.dtype(cfg["torch_dtype"])
    L = cfg["num_hidden_layers"]
    key = weights.seed_key(seed)

    def layer(key, i):
        lp = reference.make_layer(weights.layer_key(key, i), cfg, layout,
                                  dtype)
        return sparsify_for_serving(lp, layout["n"], layout["m"],
                                    layout["g"], gr=layout["gr"])

    params = jax.jit(lambda k: reference.make_outer(k, cfg, dtype))(key)
    one = jax.eval_shape(layer, key, jnp.int32(0))
    stack = jax.jit(lambda: jax.tree_util.tree_map(
        lambda s: jnp.zeros((L,) + s.shape, s.dtype), one))()

    def put(stack, key, i):
        return jax.tree_util.tree_map(lambda b, x: b.at[i].set(x), stack,
                                      layer(key, i))

    put = jax.jit(put, donate_argnums=0)
    for i in range(L):
        stack = put(stack, key, jnp.int32(i))
    params["layers"] = stack
    return jax.block_until_ready(params)


def make_engine(params, workload: dict, cfg: dict):
    """A paged engine over ``params``, with every prompt bucket and the
    decode chunk compiled (as ``serve.make_engine``)."""
    from repro.serve import Request, SamplingParams
    from repro.serve.engine import ServeEngine

    eng = workload["engine"]
    clock = serve.Clock()
    engine = ServeEngine(params, model_config(cfg), clock=clock,
                         max_slots=eng["max_slots"],
                         max_seq_len=eng["max_seq_len"], paged=True,
                         page_size=eng["page_size"],
                         decode_chunk=eng["decode_chunk"])
    rng = np.random.default_rng(0)
    warm = [Request(uid=-1 - i,
                    prompt=rng.integers(0, cfg["vocab_size"], L,
                                        dtype=np.int32),
                    max_new_tokens=eng["decode_chunk"] + 1,
                    sampling=SamplingParams(greedy=True))
            for i, L in enumerate(workload["prompt_len"]["buckets"])]
    engine.run(warm)
    return engine, clock


def padded_length(n: int) -> int:
    """``n`` positions rounded up to whole attention blocks."""
    blk = reference.QUERY_BLOCK
    return n if n <= blk else -(-n // blk) * blk


def logit_gaps(cfg: dict, seed: int, sample: list,
               control: bool = False) -> tuple:
    """``serve.logit_gaps`` with each request run alone at its own
    (rounded) length: for each served token, how far the float32
    reference's logit of it lies below the reference's best.  With
    ``control`` also the gaps of the tokens the fp8 control puts first,
    read in the same float32 reference: (gaps, control gaps or None)."""
    layout, dtype = cfg["serve_layout"], jnp.dtype(cfg["torch_dtype"])
    key = weights.seed_key(seed)
    gaps, ctl_gaps = [], []
    for r in sample:
        S, n = r["prompt_len"], len(r["tokens"])
        seq = np.concatenate([r["prompt"], r["tokens"][:-1]])
        toks = np.zeros((1, padded_length(seq.size)), np.int32)
        toks[0, :seq.size] = seq
        toks = jnp.asarray(toks)
        lg = np.asarray(reference.logits(key, cfg, layout, dtype, toks)
                        [0, S - 1:S - 1 + n], np.float64)
        rows = np.arange(n)
        gaps.append(lg.max(-1) - lg[rows, np.asarray(r["tokens"])])
        if control:
            ctl = reference.logits(key, cfg, layout, dtype, toks, "fp8")
            picked = np.asarray(jnp.argmax(ctl[0, S - 1:S - 1 + n], -1))
            ctl_gaps.append(lg.max(-1) - lg[rows, picked])

    def cat(parts):
        return np.concatenate(parts) if parts else np.zeros(0)

    return cat(gaps), cat(ctl_gaps) if control else None


def _widest(gaps) -> float:
    return float(gaps.max()) if gaps.size else float("nan")


def compare(workload: dict, cfg: dict, seed: int, recs: list,
            control: bool = False) -> tuple:
    """``serve.compare`` against this configuration's reference: every
    finished request served all the tokens it asked for (limit 0), and
    the widest gap of a sampled served token below the reference's best;
    with ``control`` the fp8 control's gap on the same sample too."""
    check = workload["check"]
    limit = check["logit_gap_limit"]
    sample = serve.check_sample(recs, seed, check["min_tokens"])
    gaps, ctl = logit_gaps(cfg, seed, sample, control)
    value = _widest(gaps)
    short = sum(1 for r in recs if r["finished"]
                and len(r["tokens"]) != r["max_new_tokens"])
    checks = {"short_answers": {"value": short, "limit": 0},
              "served_logit_gap": {"value": value, "limit": limit}}
    control_correct = None
    if control:
        checks["control_logit_gap"] = {"value": _widest(ctl),
                                       "limit": limit}
        control_correct = serve._passes(sample, short, _widest(ctl), limit)
    return serve._passes(sample, short, value, limit), checks, \
        control_correct


def run(workload: dict, cfg: dict, seed: int, seconds: float,
        trace_dir: str | None, devices, t_start: float,
        control: bool = False) -> Run:
    from bench.lib.device import device_record

    model_config(cfg)  # a system without this block fails here, at once
    engine, clock = make_engine(serving_params(cfg, seed), workload, cfg)
    plan = traffic.requests_in_window(workload, seconds, seed,
                                      cfg["vocab_size"])
    setup_s = time.perf_counter() - t_start
    if trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        w0, recs = serve.serve_plan(engine, clock, plan)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    device = device_record(devices)
    print(f"attn_window_masked_rows "
          f"{engine.stats['attn_window_masked_rows']}", file=sys.stderr)
    del engine
    gc.collect()
    correct, checks, control_correct = compare(workload, cfg, seed, recs,
                                               control)
    return Run(kind="serve", workload=workload, config=cfg, seed=seed,
               seconds=seconds, setup_s=setup_s, window=(w0, w0 + seconds),
               requests=recs, device=device, correct=correct,
               control_correct=control_correct, checks=checks,
               attempted=len(recs),
               failed=sum(1 for r in recs if not r["delivered"]))
