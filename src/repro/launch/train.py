"""Fault-tolerant training loop with integrated sparsity pipeline.

``python -m repro.launch.train --arch bert-base-sten --steps 200 --smoke``
trains the reduced config on CPU; on a real fleet the same loop runs under
the production mesh (--mesh pod).  Features:

  * sparse fine-tuning: GMP schedules (one-shot / iterative / layer-wise)
    drive per-step target sparsity; weights are FixedMaskTensors,
    re-sparsified by SameFormatSparsifier after each update, with pattern
    recomputes on the schedule's cadence (paper Figs 8-9, Table 2);
  * device-resident fast path (default): ``make_multi_step`` runs
    ``--log-every`` steps per jit call under ``lax.scan``; GMP pattern
    recomputes are an in-jit ``lax.cond`` driven by the traced step counter
    (the traced ``recompute_pattern`` path of optim/sparse_update.py), and
    metrics accumulate on device — the host syncs once per chunk, on the
    log cadence, instead of once per step;
  * ``--host-loop``: the per-step host-driven reference loop (pattern
    recomputes via host tree_map, one blocking sync per step) — kept as the
    equivalence oracle for the fast path (tests/test_train_fastpath.py);
  * checkpoint/restart: async CheckpointManager, exact data-pipeline resume
    (index-addressed batches), --resume picks up LATEST;
  * straggler watchdog + elastic hooks (dist/elastic.py);
  * the jitted step donates params/opt-state (memory) and runs fully under
    pjit when a mesh is active.
"""

from __future__ import annotations

import argparse
import functools
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs import get_arch, get_smoke
from repro.core.builder import SparsityBuilder
from repro.core.layouts import FixedMaskTensor
from repro.core.sparsifiers import ScalarFractionSparsifier
from repro.data import DataConfig, SyntheticLMPipeline
from repro.dist.elastic import StragglerWatchdog
from repro.models import init_lm, loss_fn
from repro.obs import trace as obs
from repro.obs.registry import REGISTRY
from repro.optim import (
    AdamWConfig,
    GMPSchedule,
    adamw_init,
    adamw_update,
    sparse_aware_update,
    value_and_grad_sparse,
)
from repro.optim.sparse_update import resparsify_params

__all__ = ["build_sparse_params", "retarget_sparsity", "gmp_schedule",
           "make_train_step", "make_multi_step", "stack_batches", "main"]


def build_sparse_params(params, sparsity: float, targets=("mlp", "attn.wo")):
    """Sparsify matching >=2-D weights to FixedMask via magnitude pruning
    (the paper's masked-training representation)."""
    sb = SparsityBuilder()
    for t in targets:
        sb.set_weight(f"*{t}*", ScalarFractionSparsifier(sparsity),
                      FixedMaskTensor)
    return sb.sparsify_params(params)


def retarget_sparsity(params, sparsity: float):
    """Recompute sparsity patterns at a new global sparsity level (iterative
    GMP ramp) — the host-side spelling of the exact recompute the fast path
    runs in-jit: both route through ``resparsify_params`` so there is one
    recompute policy (unstructured FixedMask leaves follow the ramp, every
    other origin/layout uses its native recompute; the static ``origin``
    aux is preserved, keeping treedefs synced with optimizer moments)."""
    return resparsify_params(params, recompute_pattern=True,
                             target_sparsity=float(sparsity))


def gmp_schedule(mode, sparsity: float, steps: int,
                 num_layers: int) -> GMPSchedule:
    """The schedule ``--gmp MODE --sparsity S --steps N`` trains under:
    one-shot by default, ramps from N/10 to 0.8 N, recomputing the pattern
    20 times along the way."""
    mode = mode or "one_shot"
    return GMPSchedule(
        mode=mode,
        target_sparsity=sparsity or 0.5,
        begin_step=0 if mode == "one_shot" else steps // 10,
        end_step=int(steps * 0.8),
        recompute_every=max(1, steps // 20),
        num_layers=num_layers,
    )


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def make_train_step(cfg, opt_cfg: AdamWConfig):
    """Single-step reference trainer (used by --host-loop): one jit call and
    one host sync per step; GMP retargets happen outside, on the host."""

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        (loss, aux), grads = value_and_grad_sparse(
            lambda p: loss_fn(p, cfg, batch, remat="none"), has_aux=True
        )(params)
        new_p, new_s, m = adamw_update(grads, opt_state, params, opt_cfg)
        new_p = resparsify_params(new_p)  # SameFormat fixed-pattern pass
        return new_p, new_s, {"loss": loss, "gnorm": m["gnorm"]}

    return train_step


def make_multi_step(cfg, opt_cfg: AdamWConfig, gmp: GMPSchedule | None,
                    n_inner: int):
    """Device-resident trainer: ``n_inner`` optimizer steps per jit call via
    ``lax.scan``.

    GMP semantics match the host reference exactly, shifted to the end of
    the step: the reference retargets *before* step ``s`` at
    ``sparsity_at(s)``; here the post-update re-sparsification of step
    ``s - 1`` recomputes the pattern when ``recompute_at(s)`` fires, at the
    same target — an in-jit ``lax.cond`` over the traced step counter (the
    traced ``recompute_pattern`` path of ``sparse_aware_update``), so no
    step ever blocks on the host.  Two boundary rules keep the final params
    bitwise-equal to the reference: the caller performs the single retarget
    at the very first step of a run (``recompute_at(start_step)``), which
    has no preceding in-jit step to piggyback on, and ``stop`` (the run's
    total step count) suppresses the retarget that would otherwise prepare
    the never-executed step ``stop``.

    Returns ``multi_step(params, opt_state, batches, step0, stop) ->
    (params, opt_state, metrics)`` where ``batches`` is a pytree of
    ``[n_inner, ...]`` arrays, ``step0`` the global index of the first step,
    and ``metrics`` holds per-step ``loss``/``gnorm`` arrays ([n_inner])
    that stay on device until the caller fetches them — the log-cadence
    flush.
    """

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def multi_step(params, opt_state, batches, step0, stop):
        stop = jnp.asarray(stop, jnp.int32)

        def inner(carry, xs):
            params, opt_state = carry
            batch, step = xs
            (loss, aux), grads = value_and_grad_sparse(
                lambda p: loss_fn(p, cfg, batch, remat="none"), has_aux=True
            )(params)
            if gmp is not None:
                nxt = step + 1
                recompute = gmp.recompute_at_traced(nxt) & (nxt < stop)
                target = gmp.sparsity_at_traced(nxt)
            else:
                recompute, target = False, None
            new_p, new_s, m = sparse_aware_update(
                lambda g_, s_, p_: adamw_update(g_, s_, p_, opt_cfg),
                grads, opt_state, params,
                recompute_pattern=recompute, target_sparsity=target,
            )
            return (new_p, new_s), {"loss": loss, "gnorm": m["gnorm"]}

        steps = jnp.asarray(step0, jnp.int32) + jnp.arange(
            n_inner, dtype=jnp.int32
        )
        (params, opt_state), metrics = jax.lax.scan(
            inner, (params, opt_state), (batches, steps)
        )
        return params, opt_state, metrics

    return multi_step


def stack_batches(data, lo: int, hi: int):
    """Host-stack the index-addressed batches for steps [lo, hi)."""
    per_step = [data.batch_at(s) for s in range(lo, hi)]
    return {
        k: jnp.asarray(np.stack([np.asarray(b[k]) for b in per_step]))
        for k in per_step[0]
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bert-base-sten")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--sparsity", type=float, default=0.0)
    ap.add_argument("--gmp", choices=["one_shot", "iterative", "layer_wise"],
                    default=None)
    ap.add_argument("--host-loop", action="store_true",
                    help="per-step host-driven reference loop (GMP retarget "
                         "and metric sync on every step)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--tuning-table", default=None, metavar="PATH",
                    help="load a repro.tune table (written by "
                         "`python -m repro.tune`) before the step "
                         "compiles, so sparse kernel routing uses "
                         "measured decisions instead of shipped defaults")
    ap.add_argument("--check", action="store_true",
                    help="run the repro.check static verifier over the "
                         "train entry before the first step compiles; "
                         "abort on ERROR diagnostics")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable the repro.obs flight recorder and write "
                         "a Chrome/Perfetto trace (train chunks, GMP "
                         "recomputes, per-layer sparsity, kernel routes) "
                         "to PATH on exit")
    args = ap.parse_args(argv)
    # the fast path chunks by --log-every; a non-positive value would spin
    # on zero-step chunks forever (and 0 was a ZeroDivisionError before)
    args.log_every = max(1, args.log_every)
    args.ckpt_every = max(1, args.ckpt_every)

    from repro.compile_cache import enable_compile_cache
    from repro.tune import load_table_cli

    enable_compile_cache()
    load_table_cli(args.tuning_table)  # --tuning-table or $REPRO_TUNE_TABLE

    if args.check:
        # after the table load on purpose: routed-config diagnostics (R6)
        # must judge the same table the run is about to train under
        from repro.check import preflight

        rc = preflight(("train",), arch=args.arch)
        if rc:
            print("repro.check: train preflight failed — not training")
            return rc

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = init_lm(key, cfg)

    gmp = None
    if args.gmp or args.sparsity > 0:
        gmp = gmp_schedule(args.gmp, args.sparsity, args.steps, cfg.n_layers)
        params = build_sparse_params(params, gmp.sparsity_at(0))

    opt_cfg = AdamWConfig(lr=args.lr)
    opt_state = adamw_init(params)

    data = SyntheticLMPipeline(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed,
    ))

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if mgr and args.resume:
        got = mgr.restore_latest({"params": params, "opt": opt_state})
        if got[0] is not None:
            start_step, tree, _ = got
            params, opt_state = tree["params"], tree["opt"]
            print(f"resumed from step {start_step}")

    watchdog = StragglerWatchdog(n_hosts=1)
    interrupted = []
    signal.signal(signal.SIGTERM, lambda *a: interrupted.append(1))

    if args.trace:
        obs.enable()
    run = _run_host_loop if args.host_loop else _run_fast
    rc = run(args, cfg, opt_cfg, gmp, params, opt_state, data, mgr,
             start_step, watchdog, interrupted)
    if args.trace:
        obs.dump(args.trace, registry_snapshot=REGISTRY.snapshot())
        print(f"wrote trace to {args.trace}")
    return rc


def _log_line(step, loss, gnorm, dt):
    print(f"step {step:5d} loss {loss:.4f} gnorm {gnorm:.3f} "
          f"({dt:.2f}s/step)", flush=True)


def _sparsity_telemetry(params, step: int) -> None:
    """Per-layer sparsity telemetry on the log cadence (flight recorder
    only — this syncs mask means to the host, so it must never run in an
    untraced hot loop).  Each FixedMask leaf becomes a registry gauge and
    one ``sparsity`` event on the train track; leaves stacked across
    layers (a leading scan axis) report per-layer means."""
    if not obs.enabled():
        return
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            params, is_leaf=lambda x: isinstance(x, FixedMaskTensor))[0]:
        if not isinstance(leaf, FixedMaskTensor):
            continue
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        mask = np.asarray(leaf.mask)
        if mask.ndim >= 3:  # stacked layers: per-layer mean over axis 0
            per_layer = 1.0 - mask.reshape(mask.shape[0], -1).mean(axis=1)
            for i, s in enumerate(per_layer):
                REGISTRY.gauge(f"train_sparsity/{name}/layer{i}").set(
                    float(s))
            obs.event("sparsity", "train", step=step, weight=name,
                      mean=round(float(per_layer.mean()), 4),
                      per_layer=[round(float(s), 4) for s in per_layer])
        else:
            s = 1.0 - float(mask.mean())
            REGISTRY.gauge(f"train_sparsity/{name}").set(s)
            obs.event("sparsity", "train", step=step, weight=name,
                      sparsity=round(s, 4))


def _interrupt_save(mgr, step, params, opt_state) -> int:
    """SIGTERM epilogue shared by both loops: blocking checkpoint at the
    number of steps completed, exit code 1."""
    print("SIGTERM: checkpointing and exiting")
    if mgr:
        mgr.save(step, {"params": params, "opt": opt_state}, blocking=True)
    return 1


def _finish(args, mgr, params, opt_state, start_step, t_start, losses) -> int:
    """Normal epilogue shared by both loops: final blocking checkpoint +
    run summary."""
    if mgr:
        mgr.save(args.steps, {"params": params, "opt": opt_state},
                 blocking=True)
    final = f"; final loss {losses[-1]:.4f}" if losses else ""
    print(f"done: {args.steps - start_step} steps in "
          f"{time.time() - t_start:.1f}s{final}")
    return 0


def _run_fast(args, cfg, opt_cfg, gmp, params, opt_state, data, mgr,
              start_step, watchdog, interrupted):
    """Device-resident loop: chunks of up to --log-every steps per jit call;
    the host touches device values once per chunk."""
    # the first step of the run has no preceding in-jit step whose cond can
    # retarget for it — apply the schedule's step-``start_step`` recompute
    # once on the host (matches the reference loop's pre-step retarget)
    if gmp and gmp.recompute_at(start_step):
        obs.event("gmp_recompute", "train", step=start_step,
                  target=gmp.sparsity_at(start_step), in_jit=False)
        params = retarget_sparsity(params, gmp.sparsity_at(start_step))

    # chunk length -> compiled trainer.  Lengths come from a bounded set
    # (log_every, the remainder to a ckpt boundary, the final remainder),
    # so at most ~3 compiles per run; aligned cadences compile once.
    steppers: dict[int, callable] = {}

    t_start = time.time()
    losses: list[float] = []
    step = start_step
    while step < args.steps:
        next_ckpt = ((step // args.ckpt_every) + 1) * args.ckpt_every \
            if mgr else args.steps
        end = min(args.steps, next_ckpt, step + args.log_every)
        n = end - step
        if n not in steppers:
            steppers[n] = make_multi_step(cfg, opt_cfg, gmp, n)

        t0 = time.time()
        with obs.span("train_chunk", "train", step0=step, steps=n):
            batches = stack_batches(data, step, end)
            params, opt_state, metrics = steppers[n](
                params, opt_state, batches, jnp.int32(step),
                jnp.int32(args.steps)
            )
            # log-cadence flush: the only host<->device sync of the chunk
            chunk_loss = np.asarray(metrics["loss"])
            chunk_gnorm = np.asarray(metrics["gnorm"])
        if gmp is not None and obs.enabled():
            # the in-jit lax.cond recomputes this chunk ran, from the same
            # schedule the traced path consults (events, not measurements)
            for s in range(step + 1, end):
                if gmp.recompute_at(s) and s < args.steps:
                    obs.event("gmp_recompute", "train", step=s,
                              target=gmp.sparsity_at(s), in_jit=True)
        _sparsity_telemetry(params, end)
        dt = (time.time() - t0) / n
        watchdog.observe(0, dt)
        losses.extend(float(l) for l in chunk_loss)

        for s in range(step, end):
            if s % args.log_every == 0 or s == args.steps - 1:
                _log_line(s, chunk_loss[s - step], chunk_gnorm[s - step], dt)
        step = end
        if mgr and step % args.ckpt_every == 0:
            mgr.save(step, {"params": params, "opt": opt_state})
        if interrupted:
            return _interrupt_save(mgr, step, params, opt_state)

    return _finish(args, mgr, params, opt_state, start_step, t_start, losses)


def _run_host_loop(args, cfg, opt_cfg, gmp, params, opt_state, data, mgr,
                   start_step, watchdog, interrupted):
    """Per-step host-driven reference loop (the pre-fastpath behavior)."""
    train_step = make_train_step(cfg, opt_cfg)

    t_start = time.time()
    losses = []
    for step in range(start_step, args.steps):
        t0 = time.time()
        batch = data.batch_at(step)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}

        # GMP schedule events (outside the jitted step: pattern recomputes
        # change which entries are nonzero, values stay jit-shaped)
        if gmp and gmp.recompute_at(step):
            obs.event("gmp_recompute", "train", step=step,
                      target=gmp.sparsity_at(step), in_jit=False)
            params = retarget_sparsity(params, gmp.sparsity_at(step))

        with obs.span("train_step", "train", step=step):
            params, opt_state, metrics = train_step(params, opt_state, batch)
        watchdog.observe(0, time.time() - t0)
        losses.append(float(metrics["loss"]))

        if step % args.log_every == 0 or step == args.steps - 1:
            _sparsity_telemetry(params, step)
            _log_line(step, losses[-1], float(metrics["gnorm"]),
                      time.time() - t0)
        if mgr and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, {"params": params, "opt": opt_state})
        if interrupted:
            return _interrupt_save(mgr, step + 1, params, opt_state)

    return _finish(args, mgr, params, opt_state, start_step, t_start, losses)


if __name__ == "__main__":
    raise SystemExit(main())
