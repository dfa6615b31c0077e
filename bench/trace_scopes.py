"""Run one cell with the program's engine spans on and, traced, reduce the
trace by the program's own names as well.

    python3 bench/trace_scopes.py --workload <cell> --seed <n> --seconds <s> \
        [--trace <0|1>] [--keep <file.xplane.pb.gz>]

This is ``bench/run.py`` with two hooks: ``repro.obs`` tracing is enabled
for the measured window only (so the engine's ``engine.*`` spans reach the
profiler's host plane), and a ``--trace 1`` profile (the default) is also
reduced by ``bench/lib/scopes.py`` before ``run.py`` deletes it (``--keep``
saves a gzipped copy).  With ``--trace 0`` it prints ``run.py``'s line of
end-to-end metrics, measured with the spans on: what tracing costs.
Traced, it prints ``run.py``'s result line, then one more JSON line:
``metrics`` (``kv_move_ms_per_step``, ``host_idle_ms_per_step``,
``admit_ms_p50``), ``breakdown`` (``device_scopes``, ``engine_idle``) and
``sums``: each program's scope time beside its device time, and the
engine-charged idle beside the window's idle.
"""

from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def main(argv=None, *, require_tpu: bool = True, root=ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", choices=("0", "1"), default="1")
    ap.add_argument("--keep", help="save the profile here, gzipped")
    args = ap.parse_args(argv)

    from bench import run as bench_run
    from bench.lib import scopes, serve, trace
    from bench.lib.registry import Benchmark
    from repro.obs import trace as obs

    found = {}
    serve_plan, reduce_trace = serve.serve_plan, trace.reduce_trace

    def traced_plan(*a, **kw):
        obs.enable()
        try:
            return serve_plan(*a, **kw)
        finally:
            obs.disable()
            obs.clear()

    def reduce_both(path, window_span, window_s, *a, **kw):
        summary = reduce_trace(path, window_span, window_s, *a, **kw)
        found["trace"] = summary
        found["scopes"] = scopes.reduce_scopes(path, window_span, window_s)
        if args.keep:
            with open(path, "rb") as src, gzip.open(args.keep, "wb") as dst:
                shutil.copyfileobj(src, dst)
        return summary

    serve.serve_plan, trace.reduce_trace = traced_plan, reduce_both
    try:
        rc = bench_run.main(["--workload", args.workload, "--seed", args.seed,
                             "--seconds", args.seconds, "--trace",
                             args.trace],
                            require_tpu=require_tpu, root=root)
    finally:
        serve.serve_plan, trace.reduce_trace = serve_plan, reduce_trace
    if rc or args.trace == "0":
        return rc
    s, tr = found["scopes"], found["trace"]
    chunk = Benchmark(pathlib.Path(root)).workload(
        args.workload)["engine"]["decode_chunk"]
    metrics = {"kv_move_ms_per_step": scopes.kv_move_ms_per_step(s, chunk),
               "host_idle_ms_per_step": scopes.host_idle_ms_per_step(s),
               "admit_ms_p50": scopes.admit_ms_p50(s)}
    sums = {"scope_s_by_program": {
                p: [sum(v for (q, _), v in s.scope_s.items() if q == p), t]
                for p, t in tr.program_s.items()},
            "engine_idle_s": [sum(s.engine_idle.values()),
                              tr.window_s - tr.busy_s]}
    print(json.dumps({"metrics": {k: v for k, v in metrics.items()
                                  if v is not None},
                      "breakdown": scopes.breakdown(s), "sums": sums}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
