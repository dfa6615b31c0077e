"""Serving CLI: the paper's sparse-inference scenario as a service.

Two modes:

* one-shot (default): prefill + decode of one fixed batch, reporting
  per-token latency for dense vs n:m:g weights (paper Fig 11 at laptop
  scale) — kept as the reference the engine is tested token-for-token
  against.
* ``--engine``: the continuous-batching engine (``repro.serve``): a queue
  of requests is served through a static slot batch with per-slot KV
  caches, admission between decode steps, and p50/p99 per-token latency /
  TTFT / throughput reporting.  With ``--sparse`` the same request trace
  is served with dense and n:m:g FFN weights side by side.

``python -m repro.launch.serve --arch bert-base-sten --smoke --sparse
--engine`` runs a reduced model on CPU and serves 8 queued requests both
ways.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, get_smoke
from repro.models import decode_step, init_lm, prefill
from repro.obs import trace as obs
from repro.obs.registry import REGISTRY
from repro.serve import Request, SamplingParams, compare_dense_sparse
from repro.serve.engine import ServeEngine, sparsify_for_serving, \
    warmup_engine

__all__ = ["main", "run_oneshot", "sparsify_for_serving"]


def run_oneshot(params, cfg, prompts: jnp.ndarray, gen_len: int):
    """The original single-batch prefill + greedy decode loop.  Returns
    (generated tokens [B, gen_len], prefill seconds, decode seconds)."""
    B, S = prompts.shape
    jit_decode = jax.jit(
        lambda p, tok, cache, pos: decode_step(p, cfg, tok, cache, pos)
    )

    t0 = time.time()
    logits, cache = prefill(params, cfg, prompts, cache_len=S + gen_len)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(gen_len - 1):
        logits, cache = jit_decode(params, tok, cache, jnp.asarray(S + i))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    return jnp.concatenate(out, axis=1), t_prefill, t_decode


def _make_requests(key, cfg, args) -> list:
    """A queue of synthetic requests with slightly staggered arrivals and
    varied prompt lengths (so admission happens mid-stream)."""
    reqs = []
    for i in range(args.requests):
        k = jax.random.fold_in(key, i)
        plen = max(4, args.prompt_len - (i % 4) * 2)
        prompt = np.asarray(
            jax.random.randint(k, (plen,), 0, cfg.vocab, jnp.int32)
        )
        reqs.append(Request(
            uid=i, prompt=prompt, max_new_tokens=args.gen_len,
            sampling=SamplingParams(greedy=True, seed=i),
            arrival_time=i * args.arrival_gap,
        ))
    return reqs


def _run_engine(args, cfg, params, key) -> int:
    reqs = _make_requests(key, cfg, args)
    max_seq = args.prompt_len + args.gen_len
    ekw = dict(max_slots=args.max_slots, max_seq_len=max_seq,
               decode_chunk=args.decode_chunk)
    if args.paged:
        if max_seq % args.page_size:
            ap_err = (f"--page-size {args.page_size} must divide "
                      f"max_seq_len {max_seq} (prompt-len + gen-len)")
            raise SystemExit(ap_err)
        ekw.update(paged=True, page_size=args.page_size,
                   num_pages=args.num_pages,
                   prefix_sharing=not args.no_prefix_sharing)
    warm = not args.no_warmup
    if args.slo_tpot_ms is not None or args.tiers:
        return _run_slo_engine(args, cfg, params, reqs, ekw, warm)
    if args.sparse:
        n, m, g = (int(v) for v in args.nm.split(":"))
        results = compare_dense_sparse(params, cfg, reqs, nm=(n, m, g),
                                       engine_kwargs=ekw, warmup=warm,
                                       tune=args.tune)
        for label, (outs, met) in results.items():
            print(met.report())
        d = results["dense"][1]
        s = results["sparse"][1]
        if d.tok_latency_p50 > 0:
            print(f"sparse/dense per-token p50 ratio: "
                  f"{s.tok_latency_p50 / d.tok_latency_p50:.2f}")
    else:
        if warm:
            warmup_engine(params, cfg, reqs, engine_kwargs=ekw,
                          tune=args.tune)
        eng = ServeEngine(params, cfg, **ekw)
        outs = eng.run(reqs)
        met = eng.metrics(label="dense")
        print(met.report())
        results = {"dense": (outs, met)}
    n_served = len(next(iter(results.values()))[0])
    kind = "paged" if args.paged else "slot"
    print(f"served {n_served} requests through "
          f"{args.max_slots}-slot continuous batching ({kind} KV cache)")
    if args.paged and not args.sparse:
        kv = eng.kv.stats
        print(f"paged KV: peak {kv['peak_pages_in_use']} pages in use, "
              f"{kv['shared_tokens']} prompt tokens prefix-shared, "
              f"{kv['cow_copies']} copy-on-write page copies, "
              f"{eng.stats['preemptions']} preemptions")
    return 0


def _run_slo_engine(args, cfg, params, reqs, ekw, warm) -> int:
    """``--engine`` with the SLO control loop: resident sparsity tiers,
    hysteresis degradation ladder, optional seeded fault injection."""
    from repro.serve import FaultConfig, FaultInjector, SLOConfig, \
        trace_events

    tiers = [t.strip() for t in (args.tiers or "dense,1:4:8-gr64").split(",")
             if t.strip()]
    slo = SLOConfig(
        tpot_ms=args.slo_tpot_ms if args.slo_tpot_ms is not None else 50.0,
        ttft_ms=args.slo_ttft_ms,
    )
    faults = None
    if args.faults:
        faults = FaultInjector(FaultConfig(
            seed=args.seed, spike_prob=0.02, error_prob=0.02,
            slow_windows=((20, 40, 3.0),),
        ))
    eng = ServeEngine(params, cfg, slo=slo, tiers=tiers, faults=faults,
                      **ekw)
    if warm:
        eng.warm_tiers(sorted({int(r.prompt.size) for r in reqs}))
    traced_after_warm = dict(trace_events())
    eng.run(reqs)
    met = eng.metrics(label="slo")
    print(met.report())
    print(f"tiers: {', '.join(tiers)} | tier switches "
          f"{eng.stats['tier_switches']} | shed {eng.stats['shed']} | "
          f"timeout {eng.stats['timeout']} | fault retries "
          f"{eng.stats['fault_retries']}")
    new_traces = {k: v - traced_after_warm.get(k, 0)
                  for k, v in trace_events().items()
                  if v != traced_after_warm.get(k, 0)}
    if new_traces:
        print(f"WARNING: serving recompiled after warmup: {new_traces}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bert-base-sten")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--sparse", action="store_true")
    ap.add_argument("--nm", default="1:4:16",
                    help="n:m:g for --sparse")
    ap.add_argument("--seed", type=int, default=0)
    # engine mode
    ap.add_argument("--engine", action="store_true",
                    help="serve a request queue through the "
                         "continuous-batching engine")
    ap.add_argument("--requests", type=int, default=8,
                    help="queued requests in --engine mode")
    ap.add_argument("--max-slots", type=int, default=4,
                    help="slot-batch size in --engine mode")
    ap.add_argument("--arrival-gap", type=float, default=0.0,
                    help="seconds between request arrivals (--engine)")
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="decode steps per jit call in --engine mode "
                         "(device-resident greedy inner loop; 1 = the "
                         "per-token host-paced reference)")
    ap.add_argument("--paged", action="store_true",
                    help="--engine mode: paged KV cache (page-table "
                         "indirection + copy-on-write prefix sharing) "
                         "instead of one full-length row per slot")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--paged); must divide "
                         "prompt-len + gen-len")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool size (--paged); default sizes the "
                         "pool to the slot cache's KV footprint")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="--paged: disable content-hash prefix sharing")
    ap.add_argument("--slo-tpot-ms", type=float, default=None,
                    help="--engine mode: enable the SLO control loop with "
                         "this per-token-latency objective (hysteresis "
                         "ladder: defer admissions -> sparser weight tier "
                         "-> shed; see docs/serving.md)")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="optional time-to-first-token objective for the "
                         "SLO attainment metric")
    ap.add_argument("--tiers", default=None,
                    help="comma-separated sparsity tiers, densest first "
                         "(e.g. 'dense,2:4,1:4:8-gr64'); implies the SLO "
                         "control loop (default SLO if --slo-tpot-ms is "
                         "not given)")
    ap.add_argument("--faults", action="store_true",
                    help="--engine mode with SLO loop: inject the "
                         "deterministic seeded fault schedule (latency "
                         "spikes, slow-decode windows, retried transient "
                         "errors) from serve/faults.py")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the pre-compile pass; reported latencies "
                         "then include XLA compile stalls")
    ap.add_argument("--tuning-table", default=None, metavar="PATH",
                    help="load a repro.tune table (written by "
                         "`python -m repro.tune`) so kernel routing uses "
                         "measured decisions instead of shipped defaults")
    ap.add_argument("--tune", action="store_true",
                    help="--engine mode: autotune the served shapes "
                         "during warmup (repro.tune warmup hook)")
    ap.add_argument("--check", action="store_true",
                    help="run the repro.check static verifier over the "
                         "serve entry before doing anything; abort on "
                         "ERROR diagnostics")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable the repro.obs flight recorder and write "
                         "a Chrome/Perfetto trace (request lifecycles, "
                         "controller decisions, fault injections, kernel "
                         "routes) to PATH on exit; open it at "
                         "https://ui.perfetto.dev")
    args = ap.parse_args(argv)
    if args.paged and not args.engine:
        ap.error("--paged requires --engine (the one-shot path has no "
                 "slot scheduler to page)")
    if (args.slo_tpot_ms is not None or args.tiers or args.faults) \
            and not args.engine:
        ap.error("--slo-tpot-ms/--slo-ttft-ms/--tiers/--faults require "
                 "--engine (the SLO control loop runs the continuous-"
                 "batching scheduler)")
    if args.faults and args.slo_tpot_ms is None and not args.tiers:
        ap.error("--faults needs the SLO control loop; pass --slo-tpot-ms "
                 "and/or --tiers")
    if args.tune and not args.engine:
        # the one-shot path has no warmup/tuning hook; accepting the flag
        # there would report an untuned run as tuned
        ap.error("--tune requires --engine")
    if args.tune and args.no_warmup:
        # tuning happens inside the warmup pass because routing lookups
        # resolve at trace time; skipping warmup would silently serve
        # default routing while reporting a "tuned" run
        ap.error("--tune requires the warmup pass; drop --no-warmup")

    from repro.compile_cache import enable_compile_cache
    from repro.tune import load_table_cli

    enable_compile_cache()
    load_table_cli(args.tuning_table)  # --tuning-table or $REPRO_TUNE_TABLE

    if args.check:
        # after the table load on purpose: routed-config diagnostics (R6)
        # must judge the same table the run is about to serve under
        from repro.check import preflight

        rc = preflight(("serve",), arch=args.arch)
        if rc:
            print("repro.check: serve preflight failed — not serving")
            return rc

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = init_lm(key, cfg)

    if args.trace:
        obs.enable()
    try:
        return _main_modes(args, cfg, params, key)
    finally:
        if args.trace:
            obs.dump(args.trace, registry_snapshot=REGISTRY.snapshot())
            print(f"wrote trace to {args.trace}")


def _main_modes(args, cfg, params, key) -> int:
    if args.engine:
        return _run_engine(args, cfg, params, key)

    if args.sparse:
        n, m, g = (int(v) for v in args.nm.split(":"))
        params = sparsify_for_serving(params, n, m, g)
        print(f"serving with {n}:{m}:{g} sparse FFN weights")

    B, S, G = args.batch, args.prompt_len, args.gen_len
    prompts = jax.random.randint(key, (B, S), 0, cfg.vocab, jnp.int32)
    gen, t_prefill, t_decode = run_oneshot(params, cfg, prompts, G)
    print(f"prefill {S} toks x {B} batch: {t_prefill * 1e3:.1f} ms")
    print(f"decode  {G - 1} steps: {t_decode / max(1, G - 1) * 1e3:.2f} "
          f"ms/token")
    print("sample:", np.asarray(gen[0, :12]))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
