"""Roofline table generator: reads the dry-run JSONs and emits the
per-(arch x shape x mesh) three-term roofline analysis (assignment
§ROOFLINE ANALYSIS) as markdown for EXPERIMENTS.md.

With ``--bench BENCH_bench.json`` it also emits the **kernel roofline**
section: every benchmark record carrying a ``roofline_ideal_us`` (the
fig6 megakernel series) as measured-vs-ideal distance, so the decode
megakernel's gap to the HW roofline lands in the same report as the
end-to-end terms.

    python -m repro.launch.roofline [--dir experiments/dryrun] [--mesh 16x16]
                                    [--bench BENCH_bench.json]
"""

from __future__ import annotations

import argparse
import json
import pathlib

from repro.launch.hlo_analysis import DEFAULT_HW_KIND, HW_BY_KIND, \
    hw_for_device


def fmt_s(x):
    if x is None:
        return "-"
    if x < 1e-3:
        return f"{x * 1e6:.1f}us"
    if x < 1.0:
        return f"{x * 1e3:.2f}ms"
    return f"{x:.2f}s"


def advice(rec) -> str:
    dom = rec["roofline"]["dominant"]
    shape = rec["shape"]
    if dom == "memory":
        if "decode" in shape or "long" in shape:
            return "shrink cache bytes/token (int8 KV, window/ring caches)"
        return "cut HBM traffic: fuse/remat less, wider tiles, bf16 interms"
    if dom == "collective":
        return "cut sync bytes: value-only sparse all-reduce, overlap, " \
               "reduce-scatter instead of all-reduce"
    return "raise MXU utilization: bigger per-chip tiles, fewer pad waste"


def load(dir_: str, mesh: str | None, tag: str = "baseline"):
    recs = []
    for p in sorted(pathlib.Path(dir_).glob("*.json")):
        r = json.loads(p.read_text())
        if mesh and r.get("mesh") != mesh:
            continue
        if r.get("tag", "baseline") != tag:
            continue
        recs.append(r)
    return recs


def table(recs, *, full: bool = True) -> str:
    lines = [
        "| arch | shape | mesh | compute | memory | collective | dominant |"
        " bound | MODEL_FLOPs/HLO | note |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in recs:
        if r.get("skipped"):
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | — | — | — | "
                f"SKIP | — | — | {r['skipped']} |"
            )
            continue
        if not r.get("ok"):
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | — | — | — | "
                f"FAIL | — | — | {r.get('error','')[:60]} |"
            )
            continue
        t = r["roofline"]
        bound = max(t["compute_s"], t["memory_s"], t["collective_s"])
        ratio = r.get("useful_flops_ratio", 0.0)
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {fmt_s(t['compute_s'])} | {fmt_s(t['memory_s'])} "
            f"| {fmt_s(t['collective_s'])} | **{t['dominant']}** "
            f"| {fmt_s(bound)} | {ratio:.2f} | {advice(r)} |"
        )
    return "\n".join(lines)


def kernel_table(bench_path: str) -> str:
    """Markdown kernel-roofline section from a ``BENCH_bench.json``:
    one row per record that carries a modelled ``roofline_ideal_us``
    (fig6's megakernel series).  Distance is measured/ideal — honest only
    when the benchmark ran on the chip ``HW`` describes; elsewhere the
    speedup column is the meaningful one."""
    doc = json.loads(pathlib.Path(bench_path).read_text())
    rows = [r for r in doc.get("results", [])
            if isinstance(r, dict) and "roofline_ideal_us" in r]
    if not rows:
        return f"(no kernel-roofline records in {bench_path})"
    lines = [
        "| kernel | us/call | sequential us | speedup | ideal us |"
        " distance |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['name']} | {r['us_per_call']:.1f} "
            f"| {r.get('sequential_us', 0.0):.1f} "
            f"| {r.get('speedup_vs_sequential', 0.0):.2f}x "
            f"| {r['roofline_ideal_us']:.2f} "
            f"| {r['us_per_call'] / r['roofline_ideal_us']:.1f}x |"
        )
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--bench", default=None,
                    help="BENCH_bench.json to render the kernel-roofline "
                         "section from (fig6 megakernel records)")
    ap.add_argument("--device-kind", default=DEFAULT_HW_KIND,
                    choices=sorted(HW_BY_KIND),
                    help="HW constants to model against (keys of "
                         "launch.hlo_analysis.HW_BY_KIND)")
    args = ap.parse_args()
    recs = load(args.dir, args.mesh, args.tag)
    kind = args.device_kind
    hw = hw_for_device(kind)
    print(f"hardware ({kind}): {hw['peak_flops_bf16']/1e12:.0f} TF/s bf16, "
          f"{hw['hbm_bw']/1e9:.0f} GB/s HBM, {hw['ici_bw']/1e9:.0f} GB/s ICI"
          " per chip\n")
    print(table(recs))
    if args.bench:
        print("\n### kernel roofline (decode megakernels)\n")
        print(kernel_table(args.bench))


if __name__ == "__main__":
    main()
