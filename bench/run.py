"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration and its
chips; its file ``bench/workloads/<cell>.json`` holds its traffic or job
and the ``kind`` of driver that runs it: the module ``bench/lib/<kind>.py``
(``serve`` so far), whose ``run`` drives one run.  With ``--trace 0`` the line carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window; each metric is computed by its reader,
``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, traced,
``breakdown``), then ``checks``: each number compared with the reference,
beside its limit.  The same numbers end standard error.  Without a TPU, or
with fewer chips than the cell asks for, the run exits 2 and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: directory for run-time files inside the checkout (listed in .gitignore)
WORK_DIR = ".bench"


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache(root: pathlib.Path) -> None:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else a fixed directory inside the checkout; every program is kept, so
    only a cell's first run in a checkout compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def metrics_line(bench, name: str, run, traced: bool) -> dict:
    out = {}
    for m in bench.metrics_for(name, traced):
        value = bench.reader(m["name"])(run)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, *, require_tpu: bool = True, root=ROOT) -> int:
    args = parse_args(argv)
    root = pathlib.Path(root)
    from bench.lib import device as dev
    from bench.lib.registry import Benchmark

    bench = Benchmark(root)
    workload = bench.workload(args.workload)
    cfg = bench.config(workload["config"])
    import jax

    if require_tpu:
        try:
            devices = dev.require_chips(workload["chips"])
        except dev.NoChipError as e:
            print(f"bench/run.py: {e}", file=sys.stderr)
            return 2
    else:
        devices = jax.devices()[:workload["chips"]]
    enable_compile_cache(root)
    driver = importlib.import_module(f"bench.lib.{workload['kind']}")
    trace_dir = None
    if args.trace:
        trace_dir = root / WORK_DIR / "trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    run = driver.run(workload, cfg, args.seed, args.seconds,
                     str(trace_dir) if trace_dir else None, devices, T_START)
    if require_tpu:
        run.peaks = dev.peaks_for(devices[0].device_kind)
    result = {"correct": bool(run.correct), "attempted": run.attempted,
              "failed": run.failed}
    device = dict(run.device)
    if trace_dir:
        from bench.lib.trace import find_xplane, reduce_trace

        run.trace = reduce_trace(find_xplane(str(trace_dir)),
                                 driver.WINDOW_SPAN, run.seconds)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
    result["metrics"] = metrics_line(bench, args.workload, run,
                                     bool(args.trace))
    result["device"] = device
    if run.trace is not None:
        result["breakdown"] = {"device_ops": run.trace.top_ops,
                               "idle_gaps": run.trace.idle_gaps}
    result["checks"] = run.checks
    for name, c in run.checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
