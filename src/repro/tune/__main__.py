"""Offline autotuner CLI.

    PYTHONPATH=src python -m repro.tune [--quick] [--out tune_table.json]

Runs the microbenchmark grid for the running device and writes (merges)
its section of the JSON tuning table:

* gemv/spmm crossover (``decode_m_max``) per (shape bucket, n:m:g, gr,
  dtype),
* the XLA spmm gathered-block cap (``spmm_block_elems``),
* lossless layout-conversion costs (``convert_cost/...``) for the
  dispatcher tie-breaker,
* the fused-QKV megakernel vs per-projection decision (``fused_qkv``)
  at the fig11 serving shapes,
* on TPU (or with ``--pallas`` anywhere): the Pallas gemv tile config
  sweep (``gemv_pallas/...``) and the Pallas spmm schedule sweep
  (``spmm_pallas/...`` — streamed vs windows-on-the-grid schedule).

``--quick`` shrinks the grid to a CI-sized smoke (a handful of shapes,
few repetitions); the resulting table is still a *valid* table — just a
coarser one.  Load a table at runtime with ``--tuning-table`` on the
launch CLIs, ``--table`` on ``benchmarks/fig11_serve.py``, or the
``REPRO_TUNE_TABLE`` environment variable.
"""

from __future__ import annotations

import argparse
import json
import time

import jax.numpy as jnp

from repro.tune import bench
from repro.tune.table import SCHEMA_VERSION, TuningTable, bucket, shape_key

DEFAULT_OUT = "tune_table.json"

# (K, R) probe shapes: serving-ish FFN projections small and large; the
# (256, 4096)/(4096, 256) pair matches the fig11 serving smoke's wi/wo
# buckets so a quick table already drives that run's routing
SHAPES_QUICK = ((256, 4096), (4096, 256))
SHAPES_FULL = ((256, 256), (1024, 1024), (256, 4096), (4096, 256),
               (1024, 4096), (4096, 1024))

# (n, m, g, gr): the serving default plus 2:4 row-shared and the paper's
# per-fiber CPU format
FMTS_QUICK = ((1, 4, 8, 64),)
FMTS_FULL = ((1, 4, 8, 64), (2, 4, 16, 64), (1, 4, 16, 1))

MS_QUICK = (1, 4, 8, 16, 32, 64)
MS_FULL = (1, 2, 4, 8, 16, 24, 32, 48, 64, 128)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.tune")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized grid (fewer shapes/formats/reps)")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="tuning-table JSON path (sections for other "
                         "devices in an existing file are preserved)")
    ap.add_argument("--pallas", action="store_true",
                    help="also sweep the Pallas gemv tile config off-TPU "
                         "(interpret mode; slow, smoke value only)")
    ap.add_argument("--skip-convert", action="store_true",
                    help="skip the layout-conversion cost sweep")
    args = ap.parse_args(argv)

    from repro.kernels import ops as kops

    shapes = SHAPES_QUICK if args.quick else SHAPES_FULL
    fmts = FMTS_QUICK if args.quick else FMTS_FULL
    ms = MS_QUICK if args.quick else MS_FULL
    dtypes = (jnp.float32,) if args.quick else (jnp.float32, jnp.bfloat16)
    reps = 3 if args.quick else 7

    table = TuningTable.for_device()
    t0 = time.time()
    print(f"repro.tune: device {table.device}, "
          f"{'quick' if args.quick else 'full'} grid")

    print("decision,key,value")
    for (K, R) in shapes:
        for (n, m, g, gr) in fmts:
            for dt in dtypes:
                crossover = bench.tune_decode_threshold(
                    table, K=K, R=R, fmt=(n, m, g), gr=gr, dtype=dt,
                    ms=ms, reps=reps,
                )
                key = shape_key("decode_m_max", K=K, R=R, fmt=(n, m, g),
                                gr=gr, dtype=dt)
                print(f"decode_m_max,{key},{crossover}")

    blk = bench.tune_spmm_block(
        table, reps=reps,
        candidates=(1 << 20, 1 << 22) if args.quick
        else (1 << 18, 1 << 20, 1 << 22, 1 << 24),
    )
    print(f"spmm_block_elems,spmm_block_elems,{blk}")

    if not args.skip_convert:
        for k, us in bench.tune_conversion_costs(table, reps=reps).items():
            print(f"convert_cost,{k},{us:.1f}")

    # fused-QKV decision at the fig11 serving shapes: the fused route is
    # the shipped default, so this either confirms it or writes a veto
    win = bench.tune_fused_qkv(table, reps=reps)
    print(f"fused_qkv,fig11-shapes,{win}")

    if kops.on_tpu() or args.pallas:
        cfg = bench.tune_gemv_pallas(table, reps=max(1, reps // 2))
        print(f"gemv_pallas,best,{json.dumps(cfg)}")
        scfg = bench.tune_spmm_pallas(table, reps=max(1, reps // 2))
        print(f"spmm_pallas,best,{json.dumps(scfg)}")
    else:
        print("gemv_pallas,skipped,(off-TPU; pass --pallas to sweep in "
              "interpret mode)")
        print("spmm_pallas,skipped,(off-TPU; pass --pallas to sweep in "
              "interpret mode)")

    table.meta.update({
        "generated_by": "python -m repro.tune"
                        + (" --quick" if args.quick else ""),
        "schema": SCHEMA_VERSION,
        "elapsed_s": round(time.time() - t0, 2),
        "shapes": [[bucket(K), bucket(R)] for K, R in shapes],
    })
    table.save(args.out)
    print(f"wrote {len(table)} entries for {table.device} to {args.out} "
          f"in {table.meta['elapsed_s']}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
