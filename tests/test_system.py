"""End-to-end behaviour tests for the whole system."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_sparse_training_reduces_loss():
    """Masked sparse training on the reduced paper model actually learns."""
    import functools

    from repro.configs import get_smoke
    from repro.core.builder import SparsityBuilder
    from repro.core.layouts import FixedMaskTensor
    from repro.core.sparsifiers import ScalarFractionSparsifier
    from repro.data import DataConfig, SyntheticLMPipeline
    from repro.models import init_lm, loss_fn
    from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                             value_and_grad_sparse)
    from repro.optim.sparse_update import resparsify_params

    cfg = get_smoke("bert-base-sten")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    sb = SparsityBuilder()
    sb.set_weight("*mlp.w*", ScalarFractionSparsifier(0.5), FixedMaskTensor)
    params = sb.sparsify_params(params)
    opt_cfg = AdamWConfig(lr=2e-3)
    state = adamw_init(params)
    data = SyntheticLMPipeline(DataConfig(vocab=cfg.vocab, seq_len=48,
                                          global_batch=8, seed=1))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, s, b):
        (loss, _), g = value_and_grad_sparse(
            lambda q: loss_fn(q, cfg, b, remat="none"), has_aux=True)(p)
        p2, s2, _ = adamw_update(g, s, p, opt_cfg)
        return resparsify_params(p2), s2, loss

    losses = []
    for i in range(25):
        b = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        params, state, loss = step(params, state, b)
        losses.append(float(loss))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1
    # masks held through the whole run
    from repro.core.layouts import FixedMaskTensor as FMT

    leaves = [l for l in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, FMT))
        if isinstance(l, FMT)]
    assert leaves
    for l in leaves:
        d = np.asarray(l.to_dense())
        m = np.asarray(l.mask)
        assert (d[~m] == 0).all()


def test_serve_cli_dense_and_sparse():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    for extra in ([], ["--sparse"]):
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", "--arch",
             "bert-base-sten", "--smoke", "--batch", "2", "--prompt-len",
             "16", "--gen-len", "4"] + extra,
            capture_output=True, text=True, env=env, timeout=900)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "ms/token" in out.stdout


def test_examples_quickstart_and_custom_layout():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    root = os.path.join(os.path.dirname(__file__), "..")
    for script in ("examples/quickstart.py", "examples/custom_layout.py"):
        out = subprocess.run([sys.executable, os.path.join(root, script)],
                             capture_output=True, text=True, env=env,
                             timeout=900)
        assert out.returncode == 0, f"{script}: {out.stderr[-2000:]}"


def test_dryrun_cli_smoke_cell():
    """The dry-run driver end-to-end on the cheapest real cell (subprocess:
    it must own the 512-device flag)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "mamba2-370m", "--shape", "decode_32k", "--out",
         "/tmp/dryrun_pytest"],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"ok": true' in out.stdout
    assert '"dominant"' in out.stdout


def test_chip_smoke_refuses_cpu():
    """Without a TPU the chip smoke run fails and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.join(os.path.dirname(__file__), "..")
    out = subprocess.run([sys.executable, "chip_smoke.py"],
                         capture_output=True, text=True, env=env,
                         timeout=300, cwd=root)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
print(CHECKOUT_CACHE_DIR)
jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))))
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(tmp_path, from_env):
    """$JAX_COMPILATION_CACHE_DIR is used as given (and receives the
    entries); without it the cache goes to the fixed in-checkout path.  The
    second case keeps the cache disabled, so no test writes the checkout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    else:
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    returned, configured, checkout = out.stdout.split()
    want = str(tmp_path) if from_env else checkout
    assert returned == configured == want
    assert checkout.endswith(".jax_cache")
    assert bool(list(tmp_path.iterdir())) == from_env
