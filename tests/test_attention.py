"""Chunked attention vs naive oracle: causal, sliding-window (incl. the
block-skipping fast path), prefix-LM, softcap, GQA grouping, tile sizes."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (append_row, chunked_attention,
                                    decode_attention, read_appended)
from repro.models.transformer import _commit_leaf

B, S, H, KV, hd = 2, 64, 4, 2, 16
KEY = jax.random.PRNGKey(0)
Q = jax.random.normal(KEY, (B, S, H, hd))
K = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd))
V = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd))


def naive(q, k, v, *, causal=True, window=None, prefix_len=0, softcap=None):
    G = H // KV
    qf = q.reshape(B, S, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgh,bckh->bqkgc", qf,
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    m = jnp.ones((S, S), bool)
    if causal:
        m &= j <= i
    if window is not None:
        m &= j > i - window
    if prefix_len:
        m |= jnp.arange(S)[None, :] < prefix_len
    s = jnp.where(m[None, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("bqkgc,bckh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, S, KV * G, hd)  # kv-major head order


@pytest.mark.parametrize("cq,ck", [(8, 8), (16, 8), (64, 64), (8, 32)])
def test_causal_matches_naive(cq, ck):
    got = chunked_attention(Q, K, V, causal=True, chunk_q=cq, chunk_k=ck)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(naive(Q, K, V)), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("window,cq,ck", [(16, 8, 8), (24, 8, 8),
                                          (16, 16, 8), (40, 8, 16)])
def test_window_block_skip_matches_naive(window, cq, ck):
    got = chunked_attention(Q, K, V, causal=True, window=window,
                            chunk_q=cq, chunk_k=ck)
    want = naive(Q, K, V, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2e-3, atol=2e-3)


def test_prefix_lm():
    got = chunked_attention(Q, K, V, causal=True, prefix_len=10,
                            chunk_q=8, chunk_k=8)
    want = naive(Q, K, V, prefix_len=10)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2e-3, atol=2e-3)


def test_softcap():
    got = chunked_attention(Q, K, V, causal=True, softcap=5.0,
                            chunk_q=16, chunk_k=16)
    want = naive(Q, K, V, softcap=5.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2e-3, atol=2e-3)


def test_non_causal():
    got = chunked_attention(Q, K, V, causal=False, chunk_q=16, chunk_k=16)
    want = naive(Q, K, V, causal=False)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2e-3, atol=2e-3)


def test_ragged_seq_padding():
    q = Q[:, :50]
    got = chunked_attention(q, K[:, :50], V[:, :50], causal=True,
                            chunk_q=16, chunk_k=16)
    assert got.shape == (B, 50, H, hd)
    assert np.isfinite(np.asarray(got, np.float32)).all()


def test_bf16_compute_dtype_close():
    got = chunked_attention(Q, K, V, causal=True, chunk_q=16, chunk_k=16,
                            compute_dtype=jnp.bfloat16)
    want = naive(Q, K, V)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=5e-2, atol=5e-2)


def test_decode_matches_last_row_of_naive():
    # cache holds S entries; decode of the last position must equal the
    # last row of full attention
    q_last = Q[:, -1:][:, :, :, :]
    got = decode_attention(q_last, K, V, jnp.asarray(S))
    want = naive(Q, K, V)[:, -1:]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# append buffer: read-only history + a chunk's new rows
# ---------------------------------------------------------------------------


def _written(history, rows, start, n, ring):
    """The reference: write rows 0..n-1 (positions start + t) into the
    history one by one, as a per-row decode step would (ring rows at
    p % S; positions past S dropped)."""
    h = np.array(history)
    S = h.shape[1]
    for b in range(h.shape[0]):
        for t in range(n):
            p = int(start[b]) + t
            if ring:
                h[b, p % S] = rows[b, t]
            elif p < S:
                h[b, p] = rows[b, t]
    return h


@pytest.mark.parametrize("ring", [False, True], ids=["linear", "ring"])
@pytest.mark.parametrize("T", [1, 3, 6])
def test_read_appended_equals_sequential_writes(ring, T):
    """After each step of a chunk, the history read through the append
    buffer is bitwise the history that writing each step's row in place
    would hold — across slots at different positions, a chunk that runs
    past the end of a linear history, and a ring shorter than the chunk
    (a row written twice keeps the later position)."""
    B, S = 3, 4
    rng = np.random.default_rng(T)
    history = jnp.asarray(rng.standard_normal((B, S, 2, 3)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((B, T, 2, 3)), jnp.float32)
    start = jnp.asarray([0, 2, 3], jnp.int32)
    buf = jnp.zeros((B, T, 2, 3), jnp.float32)
    for t in range(T):
        pos = start + t
        buf = append_row(buf, rows[:, t], start, pos)
        got = read_appended(history, buf, start, pos, ring=ring)
        want = _written(history, np.asarray(rows), np.asarray(start), t + 1,
                        ring)
        np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(buf), np.asarray(rows))


@pytest.mark.parametrize("ring", [False, True], ids=["linear", "ring"])
@pytest.mark.parametrize("T", [1, 3, 6])
def test_commit_leaf_equals_sequential_writes(ring, T):
    """Committing a chunk's buffer [L, B, T, ...] into a stacked cache
    [L, B, S, ...] leaves what the chunk's per-row writes would have."""
    L, B, S = 2, 3, 4
    rng = np.random.default_rng(10 + T)
    cache = jnp.asarray(rng.standard_normal((L, B, S, 5)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((L, B, T, 5)), jnp.float32)
    start = jnp.asarray([0, 2, 3], jnp.int32)
    got = np.asarray(_commit_leaf(cache, rows, start, ring))
    for layer in range(L):
        want = _written(cache[layer], np.asarray(rows[layer]),
                        np.asarray(start), T, ring)
        np.testing.assert_array_equal(got[layer], want)
