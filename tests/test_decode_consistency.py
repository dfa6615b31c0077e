"""Serving-path correctness: token-by-token decode from a prefilled cache
must reproduce the parallel forward's logits (teacher forcing), for each
attention family — this exercises KV caches, MLA latent caches, absorbed
decode, SSM recurrence vs chunked scan, ring caches, and hybrid fusion."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import decode_step, forward, init_lm, logits_of, prefill

B, S_PRE, S_GEN = 2, 16, 6

FAMILIES = {
    "bert-base-sten": 2e-3,   # plain GQA/MHA
    "minicpm3-4b": 2e-2,      # MLA absorbed decode vs full-rank forward
    "mamba2-370m": 2e-3,      # SSD chunked scan vs recurrence
    "hymba-1.5b": 2e-3,       # hybrid window attn + SSM
    "gemma2-9b": 2e-3,        # local/global pairs, softcaps, ring cache
    "qwen1.5-4b": 2e-3,       # QKV bias
    # GQA, LayerNorm, biased projections, non-gated MLP, and a window of 8
    # that the 22-token sequences overrun: decode masks by window what the
    # forward's chunks mask, in the same f32 arithmetic
    "starcoder2-15b": 2e-3,
}


@pytest.mark.parametrize("arch", sorted(FAMILIES))
def test_decode_matches_forward(arch):
    cfg = dataclasses.replace(get_smoke(arch), dtype="float32")
    key = jax.random.PRNGKey(0)
    params = init_lm(key, cfg)
    total = S_PRE + S_GEN
    toks = jax.random.randint(key, (B, total), 0, cfg.vocab, jnp.int32)

    # parallel forward over the whole sequence (ground truth)
    hidden, _ = forward(params, cfg, toks, remat="none")
    full_logits = np.asarray(logits_of(params, cfg, hidden),
                             dtype=np.float32)

    # prefill the first S_PRE tokens, then teacher-forced decode
    logits, cache = prefill(params, cfg, toks[:, :S_PRE], cache_len=total)
    np.testing.assert_allclose(
        np.asarray(logits, np.float32), full_logits[:, S_PRE - 1],
        rtol=FAMILIES[arch], atol=FAMILIES[arch],
    )
    for i in range(S_GEN):
        tok = toks[:, S_PRE + i][:, None]
        got, cache = decode_step(params, cfg, tok, cache,
                                 jnp.asarray(S_PRE + i))
        want = full_logits[:, S_PRE + i]
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want,
            rtol=FAMILIES[arch], atol=FAMILIES[arch],
            err_msg=f"{arch} step {i}",
        )


def test_int8_kv_cache_decode():
    """int8 KV/latent caches: teacher-forced decode stays within quantization
    tolerance of the f32-cache forward (the §Perf serving optimization)."""
    for arch in ("qwen1.5-4b", "minicpm3-4b"):
        cfg = dataclasses.replace(get_smoke(arch), dtype="float32",
                                  kv_cache_dtype="int8")
        key = jax.random.PRNGKey(0)
        params = init_lm(key, cfg)
        toks = jax.random.randint(key, (2, 24), 0, cfg.vocab, jnp.int32)
        hidden, _ = forward(params, cfg, toks, remat="none")
        full = np.asarray(logits_of(params, cfg, hidden), np.float32)
        logits, cache = prefill(params, cfg, toks[:, :16], cache_len=24)
        for i in range(4):
            tok = toks[:, 16 + i][:, None]
            got, cache = decode_step(params, cfg, tok, cache,
                                     jnp.asarray(16 + i))
            np.testing.assert_allclose(
                np.asarray(got, np.float32), full[:, 16 + i],
                atol=0.05, rtol=0.05, err_msg=f"{arch} step {i}")
