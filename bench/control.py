"""Read a cell's comparison with the reference and with its control.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed, one process runs the cell as ``run.py`` does (set-up, a
window at the cell's own load) and then reads each compared number twice:
for the program's output, and for the control, the reference computed one
precision below the configuration's (float8 for bfloat16) and put in the
program's place.  Both are judged by the same rule against the cell's
limits: ``correct`` is the program's verdict and ``control_correct`` the
control's, which has to come out false.  A limit lies between the largest
program reading over a dozen seeds or more and the smallest control
reading.  The benchmark's own
runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from run import ROOT, enable_compile_cache  # noqa: F401  (sets sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import importlib

    from bench.lib import device as dev
    from bench.lib.registry import Benchmark

    bench = Benchmark(ROOT)
    workload = bench.workload(args.workload)
    cfg = bench.config(workload["config"])
    try:
        devices = dev.require_chips(workload["chips"])
    except dev.NoChipError as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    driver = importlib.import_module(f"bench.lib.{workload['kind']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        run = driver.run(workload, cfg, seed, args.seconds, None, devices,
                         time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": run.correct,
                          "control_correct": run.control_correct,
                          "checks": run.checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
