"""Paper §6.1: distributed masked-sparse-training overhead (weak scaling).

Runs in one process over ``jax.devices()[:n]`` for n in 1..8 (fixed
per-device batch) and measures dense vs masked-sparse step time including
gradient sync, reporting scaling efficiency and the sparse-over-dense
overhead — the analogue of the paper's 128-GPU Piz Daint experiment.

It starts no child process: the process that touched JAX holds the
devices.  On a CPU host, give it virtual devices from the caller:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python benchmarks/weak_scaling.py
"""

import time

import jax

from repro.configs import get_smoke
from repro.core.builder import SparsityBuilder
from repro.core.layouts import FixedMaskTensor
from repro.core.sparsifiers import ScalarFractionSparsifier
from repro.dist.sharding import ShardingRules
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_host_mesh
from repro.models import init_lm
from repro.optim import AdamWConfig, adamw_init


def run(ndev: int, sparse: bool) -> float:
    """Median step seconds on a (ndev, 1) data mesh over the first
    ``ndev`` devices."""
    cfg = get_smoke("bert-base-sten")
    mesh = make_host_mesh(ndev, 1)
    rules = ShardingRules(batch=("data",), embed=None, heads=None, ff=None,
                          vocab=None, expert=None)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    if sparse:
        sb = SparsityBuilder()
        sb.set_weight("*mlp.w*", ScalarFractionSparsifier(0.75),
                      FixedMaskTensor)
        sb.set_weight("*attn.w*", ScalarFractionSparsifier(0.75),
                      FixedMaskTensor)
        params = sb.sparsify_params(params)
    opt = adamw_init(params)
    step = steps_mod.make_train_step(
        cfg, AdamWConfig(lr=1e-3), steps_mod.StepConfig(remat="none"),
        mesh, rules)
    B = 2 * ndev   # fixed per-device batch (weak scaling)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, 64), 0,
                                     cfg.vocab),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (B, 64), 0,
                                     cfg.vocab),
    }
    with mesh:
        jstep = jax.jit(step)
        p, o, _ = jax.block_until_ready(jstep(params, opt, batch))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            p, o, m = jstep(p, o, batch)
            jax.block_until_ready(m)
            ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def main(quick=False):
    have = len(jax.devices())
    devs = [d for d in ([1, 4] if quick else [1, 2, 4, 8]) if d <= have]
    if devs[-1] < 4:
        raise RuntimeError(
            f"weak_scaling needs at least 4 devices, found {have}; on a CPU "
            f"host set XLA_FLAGS=--xla_force_host_platform_device_count=8")
    print("devices,dense_ms,sparse_ms,dense_eff,sparse_eff,sparse_overhead")
    base_d = base_s = None
    for nd in devs:
        td, ts = run(nd, False), run(nd, True)
        base_d = base_d or td
        base_s = base_s or ts
        print(f"{nd},{td * 1e3:.1f},{ts * 1e3:.1f},"
              f"{base_d / td * 100:.0f}%,{base_s / ts * 100:.0f}%,"
              f"{(ts / td - 1) * 100:.0f}%")


if __name__ == "__main__":
    main()
