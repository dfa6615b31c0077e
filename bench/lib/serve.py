"""The serving driver: one cell of open-loop traffic through ``ServeEngine``.

Set-up draws the weights from the seed on the device, converts them to the
configuration's serving layout in one jitted call, builds the paged engine
and warms every prompt bucket and the decode chunk.  The window then hands
the engine the whole schedule through its own serving loop (``run``): each
request is admitted once its due time has passed, so the loop is open.
The engine keeps serving after the window closes until every request of
the window has finished; only what was delivered inside the window counts
towards throughput, and every request's first token counts towards TTFT,
late ones with their wait.

A decode token reaches the caller when the engine's ``step`` that produced
it returns: the engine spreads a chunk's time evenly over its tokens, so
each token's delivery time is taken as the end of the step whose span holds
the time the engine gave it.
"""

from __future__ import annotations

import bisect
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import reference, traffic, weights
from bench.lib.record import Run, model_config

#: the host span whose start opens the measured window in a trace
WINDOW_SPAN = "bench.serve"


class Clock:
    """``time.perf_counter`` from its first reading: handed to the engine,
    whose epoch is its first reading, so engine times and the harness's
    share one base."""

    def __init__(self):
        self.base = None

    def __call__(self) -> float:
        t = time.perf_counter()
        if self.base is None:
            self.base = t
        return t - self.base


def _annotate(fn, name: str, spans: list, clock: Clock):
    def wrapped(*a, **kw):
        t0 = clock()
        with jax.profiler.TraceAnnotation(name):
            out = fn(*a, **kw)
        spans.append((t0, clock()))
        return out

    return wrapped


def serving_params(cfg: dict, seed: int):
    """The configuration's weights from the seed, in its serving layout."""
    from repro.serve.engine import sparsify_for_serving

    layout = cfg["serve_layout"]
    dense = weights.make_params(weights.seed_key(seed), cfg, layout,
                                jnp.dtype(cfg["torch_dtype"]))
    params = jax.jit(lambda p: sparsify_for_serving(
        p, layout["n"], layout["m"], layout["g"], gr=layout["gr"]))(dense)
    return jax.block_until_ready(params)


def make_engine(params, workload: dict, cfg: dict):
    """A paged engine over ``params``, with every prompt bucket and the
    decode chunk compiled (jitted programs are shared across engines)."""
    from repro.serve import Request, SamplingParams
    from repro.serve.engine import ServeEngine

    eng = workload["engine"]
    clock = Clock()
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


def serve_window(engine, clock: Clock, plan: list, spans: dict):
    """Serve ``plan`` from now on; returns (window start, outputs by
    uid)."""
    from repro.serve import Request, SamplingParams

    engine.step = _annotate(engine.step, "bench.step", spans["step"], clock)
    if hasattr(engine.kv, "admit"):
        engine.kv.admit = _annotate(engine.kv.admit, "bench.admit",
                                    spans["admit"], clock)
    w0 = clock()
    reqs = [Request(uid=p.index, prompt=p.prompt,
                    max_new_tokens=p.max_new_tokens,
                    sampling=SamplingParams(greedy=True, seed=p.index),
                    arrival_time=w0 + p.due_s)
            for p in plan]
    with jax.profiler.TraceAnnotation("bench.serve"):
        outs = engine.run(reqs)
    return w0, {o.uid: o for o in outs}


def request_records(plan, outs, steps, w0: float) -> list:
    """One record per planned request, with delivery times per token."""
    ends = [e for _, e in steps]
    starts = [s for s, _ in steps]
    recs = []
    for p in plan:
        o = outs.get(p.index)
        rec = {"uid": p.index, "due": w0 + p.due_s,
               "prompt_len": int(p.prompt.size),
               "max_new_tokens": p.max_new_tokens, "finished": False,
               "admitted": None, "delivered": [], "tokens": []}
        if o is not None and o.token_times:
            delivered = [o.token_times[0]]
            for t in o.token_times[1:]:
                i = bisect.bisect_right(starts, t) - 1
                delivered.append(ends[i] if i >= 0 and ends[i] >= t else t)
            rec.update(finished=o.finish_reason in ("length", "stop"),
                       admitted=o.admitted_time, delivered=delivered,
                       tokens=[int(t) for t in o.tokens])
        recs.append(rec)
    return recs


def check_sample(recs: list, seed: int, min_tokens: int) -> list:
    """Finished requests drawn from the seed for the comparison: the one
    with the most served tokens, then others until ``min_tokens``."""
    done = [r for r in recs if r["finished"]]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r["tokens"]), r["uid"]))
    rng = np.random.default_rng([seed, 7])
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    sample, total = [longest], len(longest["tokens"])
    for r in rest:
        if total >= min_tokens:
            break
        sample.append(r)
        total += len(r["tokens"])
    return sample


def logit_gaps(cfg: dict, seed: int, sample: list, pad_to: int,
               precision: str = "f32") -> np.ndarray:
    """For each served token of ``sample``, how far the reference's logit
    of that token lies below the reference's best at that position.

    With ``precision="fp8"`` the token read at each position is the one the
    fp8 control puts first, and its gap is read in the float32 reference:
    the control put in the program's place."""
    layout, dtype = cfg["serve_layout"], jnp.dtype(cfg["torch_dtype"])
    key = weights.seed_key(seed)
    rows = max(1, 2048 // pad_to)
    gaps = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(sample), rows):
            block = sample[i:i + rows]
            toks = np.zeros((rows, pad_to), np.int32)
            for j, r in enumerate(block):
                seq = np.concatenate([r["prompt"], r["tokens"][:-1]])
                toks[j, :seq.size] = seq
            ref = reference.logits(key, cfg, layout, dtype,
                                   jnp.asarray(toks))
            ctl = None if precision == "f32" else reference.logits(
                key, cfg, layout, dtype, jnp.asarray(toks), precision)
            for j, r in enumerate(block):
                S, n = r["prompt_len"], len(r["tokens"])
                lg = np.asarray(ref[j, S - 1:S - 1 + n], np.float64)
                if ctl is None:
                    picked = np.asarray(r["tokens"])
                else:
                    picked = np.asarray(jnp.argmax(ctl[j, S - 1:S - 1 + n],
                                                   -1))
                gaps.append(lg.max(-1) - lg[np.arange(n), picked])
    return np.concatenate(gaps) if gaps else np.zeros(0)


def serve_plan(engine, clock: Clock, plan: list):
    """Serve ``plan`` through ``engine``; (window start, records)."""
    spans = {"step": [], "admit": []}
    w0, outs = serve_window(engine, clock, plan, spans)
    recs = request_records(plan, outs, spans["step"], w0)
    for r, p in zip(recs, plan):
        r["prompt"] = p.prompt
    return w0, recs


def _passes(sample: list, short: int, gap: float, limit: float) -> bool:
    return bool(sample) and all(r["finished"] for r in sample) \
        and short == 0 and gap <= limit


def compare(workload: dict, cfg: dict, seed: int, recs: list,
            control: bool = False) -> tuple:
    """(correct, checks, control_correct) of a run's served tokens: every
    finished request served all the tokens it asked for (an exact count,
    limit 0), and the widest gap of a sampled served token below the
    reference's best.  With ``control`` the fp8 control's gap on the same
    sample is read too and judged by the same rule (``control_correct``,
    else None)."""
    check = workload["check"]
    limit = check["logit_gap_limit"]
    sample = check_sample(recs, seed, check["min_tokens"])
    pad = workload["engine"]["max_seq_len"]
    gaps = logit_gaps(cfg, seed, sample, pad)
    value = float(gaps.max()) if gaps.size else float("nan")
    short = sum(1 for r in recs if r["finished"]
                and len(r["tokens"]) != r["max_new_tokens"])
    checks = {"short_answers": {"value": short, "limit": 0},
              "served_logit_gap": {"value": value, "limit": limit}}
    control_correct = None
    if control:
        ctl = logit_gaps(cfg, seed, sample, pad, precision="fp8")
        ctl_value = float(ctl.max()) if ctl.size else float("nan")
        checks["control_logit_gap"] = {"value": ctl_value, "limit": limit}
        control_correct = _passes(sample, short, ctl_value, limit)
    return _passes(sample, short, value, limit), checks, control_correct


def run(workload: dict, cfg: dict, seed: int, seconds: float,
        trace_dir: str | None, devices, t_start: float,
        control: bool = False) -> Run:
    from bench.lib.device import device_record

    engine, clock = make_engine(serving_params(cfg, seed), workload, cfg)
    plan = traffic.requests_in_window(workload, seconds, seed,
                                      cfg["vocab_size"])
    setup_s = time.perf_counter() - t_start
    if trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        w0, recs = serve_plan(engine, clock, plan)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    device = device_record(devices)
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
