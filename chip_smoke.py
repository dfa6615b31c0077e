"""End-to-end smoke run of STen's main paths on one TPU.

    python chip_smoke.py                # one chip: kernels, serve, train
    python chip_smoke.py --four-chips   # the sharded train step on 4 chips

Everything runs in this one process, through the entry points a user
calls, on the full ``bert-base-sten`` config (12 layers, d_model 768,
12 heads, d_ff 3072, vocab 30522, bf16) with random weights from
``--seed``:

* ``kernels`` — for every FFN weight of the model sparsified to n:m:g
  1:4:16 (gr=64), the Pallas ``nmg_linear`` (decode GEMV at M=8, prefill
  SpMM at M=128) against the XLA route on the same weights, within a bf16
  tolerance; one f32 projection against a float64 host product (the
  kernel's f32 path must not drop to bf16 precision); the fused QKV and
  gated-FFN decode kernels against their XLA routes; the Pallas n:m mask
  against its reference, exactly;
* ``serve`` — ``compare_dense_sparse``: the continuous-batching engine
  serves the same 8 requests (prompts of 16-128 tokens, 16 new tokens
  each) with dense and with 1:4:16 weights, after warmup; sparse decode
  must route to the Pallas GEMV and prefill to the Pallas SpMM;
* ``train`` — ``launch/train.make_multi_step`` takes 4 steps at batch
  8 x seq 128 under ``--sparsity 0.5 --gmp iterative``; losses finite.

``--four-chips`` runs only one sharded train step with FixedMask sparsity
on a (4, 1) data mesh and compares it with the same step on one device.

Results go to stdout; the last line is ``{"ok": true, "device": {...}}``
and is printed only when every phase passed on a TPU.  Without a TPU, or
outside a checkout of the repository, the script exits non-zero first.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "bert-base-sten"
NMG, GR = (1, 4, 16), 64
LR = 3e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def _bf16_close(got, want, what: str) -> float:
    """Pallas vs XLA in bf16: the outputs are rounded to bf16 after f32
    accumulation in different orders, so they may differ by an ulp; allow
    two ulps of the largest output (2^-7 x max |want|)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{what}: non-finite Pallas output"
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    assert err <= 2.0 ** -7 * scale, (
        f"{what}: Pallas vs XLA max abs diff {err:.3e} exceeds "
        f"2^-7 x {scale:.3e}")
    return err / scale


def phase_kernels(sparse_params, seed: int) -> None:
    from repro.core.layouts import GroupedNMTensor
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    mlp = sparse_params["layers"]["mlp"]
    key = jax.random.PRNGKey(seed + 1)
    worst = 0.0
    for name in ("wi", "wo"):
        stacked = mlp[name]
        assert isinstance(stacked, GroupedNMTensor), (name, type(stacked))
        n_layers = stacked.val.shape[0]
        K = stacked.dense_shape[stacked.sparse_dim % 2]
        for M in (8, 128):
            x = jax.random.normal(jax.random.fold_in(key, M), (M, K),
                                  jnp.bfloat16)
            for layer in range(n_layers):
                w = jax.tree_util.tree_map(lambda a: a[layer], stacked)
                got = kops.nmg_linear(x, w, use_pallas=True)
                want = kops.nmg_linear(x, w, use_pallas=False)
                worst = max(worst, _bf16_close(
                    got, want, f"mlp.{name} layer {layer} M={M}"))
    log(f"kernels: {2 * n_layers} FFN weights x M in (8, 128): Pallas == "
        f"XLA within 2^-7 x max|out| (worst diff / max|out| {worst:.3e})")

    # f32: the kernel runs its dots at HIGHEST precision
    from repro.core import nmg
    wd = jax.random.normal(jax.random.fold_in(key, 7), (768, 3072),
                           jnp.float32) * 0.02
    w = nmg.dense_to_grouped_nm(wd, *NMG, gr=GR, sparse_dim=0)
    x = jax.random.normal(jax.random.fold_in(key, 8), (8, 768), jnp.float32)
    got = np.asarray(kops.nmg_linear(x, w, use_pallas=True), np.float64)
    want = np.asarray(x, np.float64) @ np.asarray(w.to_dense(), np.float64)
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    assert rel <= 1e-3, f"f32 Pallas GEMV vs float64: {rel:.3e} > 1e-3"
    log(f"kernels: f32 Pallas GEMV vs float64 host product: max diff / "
        f"max|out| {rel:.3e}")

    # the decode megakernels: fused QKV (three d_model x d_model weights)
    # and the gated FFN on a packed [d_model, 2 d_ff] weight
    def rand_w(i, cols):
        wd = jax.random.normal(jax.random.fold_in(key, i), (768, cols),
                               jnp.bfloat16) * 0.02
        return nmg.dense_to_grouped_nm(wd, *NMG, gr=GR, sparse_dim=0)

    b = jax.random.normal(jax.random.fold_in(key, 10), (768, 8), jnp.bfloat16)
    ws = tuple(rand_w(11 + i, 768) for i in range(3))
    for p, name in zip(zip(kops.nmg_qkv(ws, b, out_dtype=jnp.bfloat16,
                                        use_pallas=True),
                           kops.nmg_qkv(ws, b, out_dtype=jnp.bfloat16,
                                        use_pallas=False)), "qkv"):
        _bf16_close(*p, f"fused qkv: w{name}")
    packed = rand_w(14, 2 * 3072)
    for act in ("gelu", "silu"):
        _bf16_close(
            kops.nmg_ffn(packed, b, act=act, out_dtype=jnp.bfloat16,
                         use_pallas=True),
            kops.nmg_ffn(packed, b, act=act, out_dtype=jnp.bfloat16,
                         use_pallas=False), f"fused ffn ({act})")
    log("kernels: fused QKV and gated FFN (gelu, silu) Pallas == XLA within "
        "2^-7 x max|out|")

    xm = jax.random.normal(jax.random.fold_in(key, 9), (3072, 768))
    for n, m in ((1, 4), (2, 4)):
        got = np.asarray(kops.nm_mask(xm, n, m, use_pallas=True))
        want = np.asarray(kref.nm_mask_ref(xm, n, m))
        assert (got == want).all(), f"nm_mask {n}:{m}: Pallas != reference"
    log("kernels: Pallas nm_mask == reference (1:4, 2:4) on [3072, 768]")


def phase_serve(cfg, params, seed: int) -> None:
    from repro.kernels import ops as kops
    from repro.serve import Request, SamplingParams, compare_dense_sparse

    gen, n_req, lens = 16, 8, (16, 64, 128)
    rng = np.random.default_rng(seed)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, lens[i % len(lens)],
                                        dtype=np.int32),
                    max_new_tokens=gen,
                    sampling=SamplingParams(greedy=True, seed=i))
            for i in range(n_req)]
    ekw = dict(max_slots=4, max_seq_len=max(lens) + gen)
    kops.reset_kernel_counters()
    t0 = time.perf_counter()
    results = compare_dense_sparse(params, cfg, reqs, nm=NMG, gr=GR,
                                   engine_kwargs=ekw, warmup=True)
    log(f"serve: warmup + dense and sparse runs {time.perf_counter() - t0:.1f}"
        f" s (prompt lengths {sorted({int(r.prompt.size) for r in reqs})}, "
        f"{ekw['max_slots']} slots)")
    for label, (outs, met) in results.items():
        log(met.report())
        assert len(outs) == n_req, (label, len(outs))
        for o in outs:
            assert o.finish_reason == "length", (label, o.uid,
                                                 o.finish_reason)
            assert len(o.tokens) == gen, (label, o.uid, len(o.tokens))
            assert all(0 <= t < cfg.vocab for t in o.tokens), (label, o.uid)
        log(f"serve[{label}]: {len(outs)} requests, "
            f"{sum(len(o.tokens) for o in outs)} tokens generated, "
            f"{sum(o.prompt_len for o in outs)} prompt tokens")
    routes = kops.kernel_counters()
    log("serve: kernel routes " + json.dumps(
        {f"{k}/{p}": c for (k, p), c in sorted(routes.items())}))
    for kernel in ("nmg_gemv", "nmg_spmm"):
        assert routes.get((kernel, "pallas")), f"{kernel} never took Pallas"
        assert not routes.get((kernel, "xla")), f"{kernel} took XLA"
    log("serve: ops on XLA on this path: none of the n:m:g matmuls")


def phase_train(cfg, seed: int, steps: int = 4) -> None:
    from repro.data import DataConfig, SyntheticLMPipeline
    from repro.launch import train
    from repro.models import init_lm
    from repro.optim import AdamWConfig, adamw_init

    t0 = time.perf_counter()
    gmp = train.gmp_schedule("iterative", 0.5, steps, cfg.n_layers)
    params = train.build_sparse_params(init_lm(jax.random.PRNGKey(seed), cfg),
                                       gmp.sparsity_at(0))
    if gmp.recompute_at(0):
        params = train.retarget_sparsity(params, gmp.sparsity_at(0))
    opt = adamw_init(params)
    data = SyntheticLMPipeline(DataConfig(vocab=cfg.vocab, seq_len=128,
                                          global_batch=8, seed=seed))
    batches = train.stack_batches(data, 0, steps)
    multi = train.make_multi_step(cfg, AdamWConfig(lr=LR), gmp, steps)
    args = (params, opt, batches, jnp.int32(0), jnp.int32(steps))
    jax.block_until_ready(args)
    t1 = time.perf_counter()
    compiled = multi.lower(*args).compile()
    t2 = time.perf_counter()
    _, _, metrics = compiled(*args)
    losses = np.asarray(metrics["loss"])
    t3 = time.perf_counter()
    log(f"train: {steps} steps, batch 8 x seq 128, sparsity 0.5 iterative "
        f"GMP: set-up {t1 - t0:.1f} s, compile {t2 - t1:.1f} s, run "
        f"{t3 - t2:.2f} s, losses {[round(float(v), 4) for v in losses]}")
    assert losses.shape == (steps,) and np.isfinite(losses).all(), losses


def phase_four_chips(cfg, seed: int) -> None:
    from repro.data import DataConfig, SyntheticLMPipeline
    from repro.dist.sharding import ShardingRules, param_specs, \
        tree_shardings
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_sparse_params
    from repro.models import init_lm
    from repro.optim import AdamWConfig, adamw_init

    devices = jax.devices()
    assert len(devices) >= 4, f"--four-chips needs 4 devices, {len(devices)}"
    params = build_sparse_params(init_lm(jax.random.PRNGKey(seed), cfg), 0.5)
    opt = adamw_init(params)
    data = SyntheticLMPipeline(DataConfig(vocab=cfg.vocab, seq_len=128,
                                          global_batch=8, seed=seed))
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
    rules = ShardingRules()

    def one_step(mesh):
        step = steps_mod.make_train_step(
            cfg, AdamWConfig(lr=LR), steps_mod.StepConfig(remat="none"),
            mesh, rules)
        p_sh = tree_shardings(param_specs(params, rules, mesh), mesh)
        with mesh:
            p = jax.device_put(params, p_sh)
            t0 = time.perf_counter()
            out = jax.block_until_ready(jax.jit(step)(p, opt, batch))
        log(f"four-chips: mesh {dict(mesh.shape)} step incl. compile "
            f"{time.perf_counter() - t0:.1f} s, loss "
            f"{float(out[2]['loss']):.5f}, gnorm {float(out[2]['gnorm']):.5f}")
        return jax.device_get(out)

    p1, _, m1 = one_step(make_host_mesh(1, 1))
    p4, _, m4 = one_step(make_host_mesh(4, 1))
    for d in devices[:4]:
        st = d.memory_stats() or {}
        log(f"four-chips: {d} bytes_in_use {st.get('bytes_in_use')} "
            f"peak_bytes_in_use {st.get('peak_bytes_in_use')}")

    for k in ("loss", "gnorm"):
        a, b = float(m1[k]), float(m4[k])
        assert abs(a - b) <= 2e-2 * abs(a), f"{k}: 1 device {a} vs 4 {b}"
    # one AdamW step moves each weight by about lr * sign(grad); an
    # element whose gradient changes sign between the two reductions
    # moves 2 lr apart, so allow that plus bf16 rounding
    worst = 0.0
    leaves1 = jax.tree_util.tree_leaves(p1)
    leaves4 = jax.tree_util.tree_leaves(p4)
    assert len(leaves1) == len(leaves4)
    for a, b in zip(leaves1, leaves4):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        tol = 2.5 * LR + 2.0 ** -7 * np.abs(a)
        assert (np.abs(a - b) <= tol).all(), "updated params disagree"
        worst = max(worst, float(np.abs(a - b).max()))
    log(f"four-chips: 1-device vs (4, 1) mesh: loss and gnorm within 2%, "
        f"params within 2.5 lr + 2^-7 |p| (worst abs diff {worst:.3e})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_arch

    dev = jax.devices()[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
        f"count {len(jax.devices())}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); this run "
              f"measures the chip and does not fall back", file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")

    cfg = get_arch(ARCH)
    log(f"config: {ARCH} {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
        f"{cfg.dtype}")
    if args.four_chips:
        phases = [("four_chips", lambda: phase_four_chips(cfg, args.seed))]
    else:
        from repro.models import init_lm
        from repro.serve.engine import sparsify_for_serving

        params = init_lm(jax.random.PRNGKey(args.seed), cfg)
        phases = [
            ("kernels", lambda: phase_kernels(
                sparsify_for_serving(params, *NMG, gr=GR), args.seed)),
            ("serve", lambda: phase_serve(cfg, params, args.seed)),
            ("train", lambda: phase_train(cfg, args.seed)),
        ]
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        log(f"phase {name}: ok in {time.perf_counter() - t0:.1f} s")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
