"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Every benchmark runs in this one process (``weak_scaling`` uses the
process's own devices; on a CPU host give it virtual ones with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

Prints each benchmark's CSV block plus a trailing summary in
``name,us_per_call,derived`` form, and writes the same summary as
machine-readable JSON to ``BENCH_bench.json`` (the file the perf
trajectory ingests).
"""

import argparse
import time
import traceback

from repro.compile_cache import enable_compile_cache
from repro.ioutil import atomic_write_json

from benchmarks import (
    fig6_spmm,
    fig7_energy,
    fig8_finetune,
    fig9_overheads,
    fig9_train,
    fig10_gemm,
    fig11_e2e,
    fig11_serve,
    table2_productivity,
    weak_scaling,
)

BENCHES = [
    ("fig6_spmm", fig6_spmm.main),
    ("fig7_energy", fig7_energy.main),
    ("fig10_gemm", fig10_gemm.main),
    ("fig9_overheads", fig9_overheads.main),
    ("fig9_train", fig9_train.main),
    ("fig11_e2e", fig11_e2e.main),
    ("fig11_serve", fig11_serve.main),
    ("fig8_finetune", fig8_finetune.main),
    ("table2_productivity", table2_productivity.main),
    ("weak_scaling", weak_scaling.main),
]

SUMMARY_JSON = "BENCH_bench.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=SUMMARY_JSON,
                    help="summary JSON output path")
    args = ap.parse_args()
    enable_compile_cache()

    if args.only and args.only not in {n for n, _ in BENCHES}:
        raise SystemExit(
            f"--only {args.only!r} matches no benchmark; known: "
            + ", ".join(n for n, _ in BENCHES)
        )

    summary = []
    detail = []  # per-measurement records a benchmark returns (fig6_spmm)
    for name, fn in BENCHES:
        if args.only and args.only != name:
            continue
        print(f"\n=== {name} " + "=" * (60 - len(name)), flush=True)
        t0 = time.time()
        try:
            ret = fn(quick=args.quick)
            summary.append((name, time.time() - t0, "ok"))
            if isinstance(ret, list):
                detail.extend(r for r in ret if isinstance(r, dict))
        except Exception as e:  # keep the harness going
            traceback.print_exc()
            summary.append((name, time.time() - t0, f"FAIL:{type(e).__name__}"))

    print("\n=== summary ===")
    print("name,us_per_call,derived")
    for name, secs, status in summary:
        print(f"{name},{secs * 1e6:.0f},{status}")

    results = [
        {"name": name, "us_per_call": secs * 1e6, "derived": status}
        for name, secs, status in summary
    ]
    atomic_write_json(args.json, {
        "benchmark": "bench",
        "quick": bool(args.quick),
        # wall time per benchmark, then each benchmark's own
        # per-measurement records (e.g. fig6_spmm's per-(path, M)
        # kernel timings)
        "results": results + detail,
    })
    print(f"wrote {args.json}")

    if any("FAIL" in s for _, _, s in summary):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
