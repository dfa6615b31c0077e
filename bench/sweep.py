"""Find a serving cell's knee: the highest offered rate whose queue does
not grow over a window.

    python3 bench/sweep.py --workload <cell> --rates 10,20,40 --seconds 15

One process builds the cell's weights once and serves the cell's traffic
at each rate in turn, each through a fresh engine (compiled programs are
shared).  One JSON line per rate reports throughput, tails and the queue:
the median wait of the requests due in the window's first and last thirds,
and how many were still waiting for a slot when the window closed.  A rate
is sustained when the last third waits no more than twice the first third
plus 20 ms and fewer requests than there are slots are left waiting.  The
cell then runs at 0.8x the highest sustained rate.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from run import ROOT, enable_compile_cache  # noqa: F401  (sets sys.path)


def sweep_point(recs: list, w0: float, seconds: float, slots: int) -> dict:
    import numpy as np

    end = w0 + seconds
    third = seconds / 3

    def wait(lo, hi):
        w = [r["admitted"] - r["due"] for r in recs
             if lo <= r["due"] - w0 < hi and r["admitted"] is not None]
        return float(np.median(w)) if w else float("nan")

    first, last = wait(0, third), wait(2 * third, seconds)
    waiting = sum(1 for r in recs if r["admitted"] is None
                  or r["admitted"] > end)
    delivered = sum(1 for r in recs for t in r["delivered"] if t < end)
    ttft = [r["delivered"][0] - r["due"] for r in recs if r["delivered"]]
    return {"requests": len(recs), "output_tok_s": delivered / seconds,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
            "wait_first_third_ms": first * 1e3,
            "wait_last_third_ms": last * 1e3,
            "waiting_at_close": waiting,
            "sustained": bool(last <= 2 * first + 0.02 and waiting < slots)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from bench.lib import device as dev
    from bench.lib import serve, traffic
    from bench.lib.registry import Benchmark

    bench = Benchmark(ROOT)
    workload = bench.workload(args.workload)
    cfg = bench.config(workload["config"])
    try:
        dev.require_chips(workload["chips"])
    except dev.NoChipError as e:
        print(f"bench/sweep.py: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    params = serve.serving_params(cfg, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        engine, clock = serve.make_engine(params, workload, cfg)
        plan = traffic.requests_in_window(workload, args.seconds, args.seed,
                                          cfg["vocab_size"], rate_hz=rate)
        t = time.perf_counter()
        w0, recs = serve.serve_plan(engine, clock, plan)
        point = sweep_point(recs, w0, args.seconds,
                            workload["engine"]["max_slots"])
        point.update(rate_hz=rate, drain_s=time.perf_counter() - t
                     - args.seconds)
        print(json.dumps(point), flush=True)
        del engine
    return 0


if __name__ == "__main__":
    sys.exit(main())
