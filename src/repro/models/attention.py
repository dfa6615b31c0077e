"""Attention: RoPE, memory-bounded chunked softmax attention (causal /
sliding-window / prefix-LM / softcap), GQA and MLA (latent) variants with
KV-cache decode paths.

The chunked attention streams KV blocks with an online-softmax
(running max / normalizer) under a double lax.scan, so peak memory is
O(B * cq * H * ck) instead of O(B * H * S^2) — required for the 32k-prefill
dry-run cells and keeps the HLO small for 1-CPU compiles.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import (MLAConfig, ModelConfig, dense_init, mm,
                                 mm_fused_qkv)

__all__ = [
    "rope",
    "chunked_attention",
    "decode_attention",
    "init_gqa",
    "apply_gqa",
    "decode_gqa",
    "out_proj",
    "init_mla",
    "apply_mla",
    "decode_mla",
    "pos_vec",
    "append_row",
    "appended_rows",
    "read_appended",
]

NEG_INF = -1e30


def pos_vec(pos, B: int) -> jnp.ndarray:
    """Normalize a decode position to a per-batch [B] int32 vector.

    Scalar ``pos`` (the classic single-sequence decode loop) broadcasts to
    all rows; a [B] vector is passed through — the continuous-batching
    engine drives every slot at its own position."""
    p = jnp.asarray(pos, jnp.int32)
    if p.ndim == 0:
        p = jnp.broadcast_to(p[None], (B,))
    return p


# ---------------------------------------------------------------------------
# append buffer: a decode chunk's new cache rows, beside a read-only history
# ---------------------------------------------------------------------------


def append_row(buf, row, start, pos):
    """Write each slot's new row into its append buffer: ``buf`` [B, T,
    ...] holds the rows of positions ``start .. start + T - 1``; ``row``
    [B, ...] is position ``pos`` (start <= pos < start + T)."""
    return buf.at[jnp.arange(buf.shape[0]), pos - start].set(row)


def appended_rows(start, pos, T: int, S: int, *, ring: bool = False):
    """[B, T] history rows of the append buffer's rows once the step at
    ``pos`` has written: buffer row t holds position start + t, at row
    (start + t) % S of a ring (window-sized) history.  Rows not yet
    written, and ring rows a later position overwrote, map to the
    out-of-range row S, which a scatter drops — as it drops positions past
    a linear history's end."""
    t = jnp.arange(T, dtype=jnp.int32)[None, :]
    step = (pos - start)[:, None]              # the row written at pos
    p = start[:, None] + t
    keep = t <= step
    if ring:
        keep &= t > step - S                   # not overwritten by t + S
        p = p % S
    return jnp.where(keep, p, S)


def read_appended(history, buf, start, pos, *, ring: bool = False):
    """The cache rows a decode step attends over, without writing the
    history: row s of slot b is ``buf[b, p - start[b]]`` where the position
    p that row holds was written this chunk (start[b] <= p <= pos[b]), and
    ``history[b, s]`` otherwise.  history [B, S, ...] is read-only, buf
    [B, T, ...] the append buffer.  The buffer's written rows are scattered
    into a copy of the history (:func:`appended_rows`), so the result is
    bitwise the history a per-row write would have made."""
    B, S, T = history.shape[0], history.shape[1], buf.shape[1]
    rows = appended_rows(start, pos, T, S, ring=ring)
    return history.at[jnp.arange(B)[:, None], rows].set(buf)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, S, H, hd] (hd even); positions: [B, S] or [S]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked attention (prefill / training)
# ---------------------------------------------------------------------------


def _block_mask(qpos, kpos, *, causal, window, prefix_len):
    """qpos [cq], kpos [ck] -> bool [cq, ck] (True = visible)."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        m &= kpos[None, :] > (qpos[:, None] - window)
    if prefix_len:
        m |= kpos[None, :] < prefix_len
    return m


def chunked_attention(q, k, v, *, causal: bool = True,
                      window: Optional[int] = None, prefix_len: int = 0,
                      softcap: Optional[float] = None, chunk_q: int = 512,
                      chunk_k: int = 512, q_offset: int = 0,
                      compute_dtype=jnp.float32) -> jnp.ndarray:
    """q: [B, Sq, H, hd]; k, v: [B, Sk, KV, hd] (H % KV == 0).
    Online-softmax over KV chunks; returns [B, Sq, H, hd] in q.dtype."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, hdv = v.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    cq = min(chunk_q, Sq)
    ck = min(chunk_k, Sk)
    # pad to chunk multiples
    Sq_p, Sk_p = -(-Sq // cq) * cq, -(-Sk // ck) * ck
    qp = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    nq, nk = Sq_p // cq, Sk_p // ck

    cdt = jnp.dtype(compute_dtype)
    qb = qp.reshape(B, nq, cq, KV, G, hd).astype(cdt)
    kb = kp.reshape(B, nk, ck, KV, hd).astype(cdt)
    vb = vp.reshape(B, nk, ck, KV, hdv).astype(cdt)

    kb_s = jnp.moveaxis(kb, 1, 0)  # [nk, B, ck, KV, hd]
    vb_s = jnp.moveaxis(vb, 1, 0)

    # sliding-window block skipping: a query chunk starting at qi*cq only
    # sees kv blocks intersecting (qi*cq - window, qi*cq + cq); with causal
    # masking that is a CONSTANT number of blocks, so the inner scan length
    # drops from nk to nwin — the structural local-attention win (used by
    # hymba / gemma2-local layers; a §Perf hillclimb result).
    nwin = nk
    if window is not None and causal and not prefix_len:
        nwin = min(nk, (window + cq) // ck + 2)

    def q_step(_, qi_and_block):
        qi, qblk = qi_and_block  # qblk [B, cq, KV, G, hd]
        qpos = q_offset + qi * cq + jnp.arange(cq)

        if nwin < nk:
            kstart = jnp.clip((qi * cq - window) // ck, 0, nk - nwin)
        else:
            kstart = jnp.asarray(0)

        def kv_step(carry, j):
            m_run, l_run, acc = carry
            ki = kstart + j
            kblk = jax.lax.dynamic_index_in_dim(kb_s, ki, 0, False)
            vblk = jax.lax.dynamic_index_in_dim(vb_s, ki, 0, False)
            kpos = ki * ck + jnp.arange(ck)
            valid = kpos < Sk
            s = jnp.einsum("bqkgh,bckh->bqgkc", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            mask = _block_mask(qpos, kpos, causal=causal, window=window,
                               prefix_len=prefix_len)
            mask = mask[None, :, None, None, :] & valid[None, None, None, None, :]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bqgkc,bckh->bqgkh", p.astype(cdt), vblk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, cq, G, KV), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, cq, G, KV), jnp.float32)
        a0 = jnp.zeros((B, cq, G, KV, hdv), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), jnp.arange(nwin)
        )
        out = acc / jnp.maximum(l_f, 1e-20)[..., None]  # [B, cq, G, KV, hdv]
        return None, out

    _, outs = jax.lax.scan(
        q_step, None, (jnp.arange(nq), jnp.moveaxis(qb, 1, 0))
    )  # [nq, B, cq, G, KV, hdv]
    # restore head order: the accumulator is [..., G, KV, hdv] but the
    # caller's head index is h = kv * G + g (kv-major, matching the input
    # reshape and the decode path) — swap before flattening.
    out = jnp.moveaxis(outs, 0, 1)  # [B, nq, cq, G, KV, hdv]
    out = jnp.swapaxes(out, 3, 4).reshape(B, Sq_p, KV * G, hdv)[:, :Sq]
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, softcap=None,
                     window: Optional[int] = None) -> jnp.ndarray:
    """Single-token decode: q [B, 1, H, hd]; caches [B, S, KV, hd];
    cache_len [] or [B] current valid length(s) (the new token is already
    written).  A per-batch ``cache_len`` is the continuous-batching serving
    path: every slot attends over its own prefix while sharing one
    static-shape cache."""
    B, _, H, hd = q.shape
    S = k_cache.shape[1]
    KV = k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qf = q.reshape(B, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, k_cache.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(S)
    cl = jnp.asarray(cache_len)
    if cl.ndim == 1:
        cl = cl[:, None, None, None]  # [B, 1, 1, 1] broadcast over heads/seq
    valid = pos[None, None, None, :] < cl
    if window is not None:
        valid &= pos[None, None, None, :] > (cl - 1 - window)
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA (with optional QKV bias, local window, softcap)
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: ModelConfig):
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H * hd), cfg.jdtype),
        "wk": dense_init(ks[1], (D, KV * hd), cfg.jdtype),
        "wv": dense_init(ks[2], (D, KV * hd), cfg.jdtype),
        "wo": dense_init(ks[3], (H * hd, D), cfg.jdtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), cfg.jdtype)
        p["bk"] = jnp.zeros((KV * hd,), cfg.jdtype)
        p["bv"] = jnp.zeros((KV * hd,), cfg.jdtype)
    if cfg.proj_bias:
        p["bo"] = jnp.zeros((D,), cfg.jdtype)
    return p


def _qkv(p, x, cfg: ModelConfig, positions):
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    # one megakernel launch for all three projections when the weights are
    # grouped n:m:g and x is decode-shaped; bitwise-equal mm() fallback
    # otherwise
    q, k, v = mm_fused_qkv(x, p["wq"], p["wk"], p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def out_proj(p, a):
    """The output projection ``wo``, with its bias ``bo`` where the
    configuration has one (``proj_bias``)."""
    y = mm(a, p["wo"])
    return y + p["bo"] if "bo" in p else y


def apply_gqa(p, x, cfg: ModelConfig, *, is_local=False, prefix_len=0,
              positions=None, causal=True):
    B, S, D = x.shape
    if positions is None:
        positions = jnp.arange(S)
    q, k, v = _qkv(p, x, cfg, positions)
    window = cfg.local_window if is_local else None
    out = chunked_attention(
        q, k, v, causal=causal, window=window, prefix_len=prefix_len,
        softcap=cfg.attn_softcap, chunk_q=cfg.attn_chunk_q,
        chunk_k=cfg.attn_chunk_k, compute_dtype=cfg.attn_dtype,
    )
    return out_proj(p, out.reshape(B, S, -1)), (k, v)


def decode_gqa(p, x, cfg: ModelConfig, cache, pos, *, is_local=False):
    """x [B, 1, D]; cache {'k','v'} [B, S, KV, hd]; pos [] or [B] int32."""
    B = x.shape[0]
    pv = pos_vec(pos, B)
    q, k, v = _qkv(p, x, cfg, pv[:, None])
    rows = jnp.arange(B)
    kc = cache["k"].at[rows, pv].set(k[:, 0])
    vc = cache["v"].at[rows, pv].set(v[:, 0])
    window = cfg.local_window if is_local else None
    out = decode_attention(q, kc, vc, pv + 1, softcap=cfg.attn_softcap,
                           window=window)
    return out_proj(p, out.reshape(B, 1, -1)), {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (MiniCPM3 / DeepSeek family)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig):
    mla: MLAConfig = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    qk_hd = mla.qk_nope_head_dim + mla.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    return {
        "wdq": dense_init(ks[0], (D, mla.q_lora_rank), cfg.jdtype),
        "wuq": dense_init(ks[1], (mla.q_lora_rank, H * qk_hd), cfg.jdtype),
        "wdkv": dense_init(ks[2], (D, mla.kv_lora_rank), cfg.jdtype),
        "wuk": dense_init(
            ks[3], (mla.kv_lora_rank, H * mla.qk_nope_head_dim), cfg.jdtype
        ),
        "wuv": dense_init(
            ks[4], (mla.kv_lora_rank, H * mla.v_head_dim), cfg.jdtype
        ),
        "wkr": dense_init(ks[5], (D, mla.qk_rope_head_dim), cfg.jdtype),
        "wo": dense_init(ks[6], (H * mla.v_head_dim, D), cfg.jdtype),
        "q_norm": jnp.ones((mla.q_lora_rank,), cfg.jdtype),
        "kv_norm": jnp.ones((mla.kv_lora_rank,), cfg.jdtype),
    }


def _rms(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def apply_mla(p, x, cfg: ModelConfig, *, positions=None, causal=True):
    mla: MLAConfig = cfg.mla
    B, S, D = x.shape
    H = cfg.n_heads
    nd, rd, vd = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    if positions is None:
        positions = jnp.arange(S)

    cq = _rms(mm(x, p["wdq"]), p["q_norm"])
    q = (mm(cq, p["wuq"])).reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    ckv = _rms(mm(x, p["wdkv"]), p["kv_norm"])
    k_nope = (mm(ckv, p["wuk"])).reshape(B, S, H, nd)
    v = (mm(ckv, p["wuv"])).reshape(B, S, H, vd)
    k_rope = rope((mm(x, p["wkr"])).reshape(B, S, 1, rd), positions,
                  cfg.rope_theta)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rd))], axis=-1
    )
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = chunked_attention(q_full, k, v, causal=causal,
                            chunk_q=cfg.attn_chunk_q,
                            chunk_k=cfg.attn_chunk_k,
                            compute_dtype=cfg.attn_dtype)
    return mm(out.reshape(B, S, -1), p["wo"]), ckv, k_rope


def decode_mla(p, x, cfg: ModelConfig, history, buf, start, pos,
               q_cache=None, dq_cache=None):
    """Absorbed-MLA decode over the *compressed* cache (the serving memory
    win that motivates MLA): history = {'ckv' [B, S, r], 'kr' [B, S, rd]},
    read-only; buf the same leaves as [B, T, ...] append buffers (see
    :func:`read_appended`), into which this step's latents are written.
    Returns (y, updated buf).

    Scores in latent space: q_nope is absorbed through W_uk so attention
    reads c_kv directly; output re-expands through W_uv.
    """
    mla: MLAConfig = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    nd, rd, vd = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    r = mla.kv_lora_rank
    pv = pos_vec(pos, B)
    positions = pv[:, None]

    cq = _rms(mm(x, p["wdq"]), p["q_norm"])
    q = (mm(cq, p["wuq"])).reshape(B, 1, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    ckv_t = _rms(mm(x, p["wdkv"]), p["kv_norm"])          # [B, 1, r]
    kr_t = rope((mm(x, p["wkr"])).reshape(B, 1, 1, rd), positions,
                cfg.rope_theta).reshape(B, 1, rd)
    if q_cache is not None:
        ckv_t, kr_t = q_cache(ckv_t, cfg), q_cache(kr_t, cfg)
    sv = pos_vec(start, B)
    ckv_b = append_row(buf["ckv"], ckv_t[:, 0], sv, pv)
    kr_b = append_row(buf["kr"], kr_t[:, 0], sv, pv)
    ckv = read_appended(history["ckv"], ckv_b, sv, pv)
    kr = read_appended(history["kr"], kr_b, sv, pv)
    ckv_r = dq_cache(ckv) if dq_cache is not None else ckv
    kr_r = dq_cache(kr) if dq_cache is not None else kr

    # absorb: q' = q_nope @ W_uk(head)  -> latent space   [B, H, r]
    wuk = p["wuk"].reshape(r, H, nd)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(jnp.float32),
                       wuk.astype(jnp.float32))
    s = jnp.einsum("bhr,bsr->bhs", q_lat, ckv_r.astype(jnp.float32))
    s += jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                    kr_r.astype(jnp.float32))
    s *= 1.0 / math.sqrt(nd + rd)
    S = ckv.shape[1]
    valid = jnp.arange(S)[None, None, :] < (pv + 1)[:, None, None]
    s = jnp.where(valid, s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    out_lat = jnp.einsum("bhs,bsr->bhr", pattn, ckv_r.astype(jnp.float32))
    wuv = p["wuv"].reshape(r, H, vd)
    out = jnp.einsum("bhr,rhv->bhv", out_lat, wuv.astype(jnp.float32))
    y = mm(out.reshape(B, 1, H * vd).astype(x.dtype), p["wo"])
    return y, {"ckv": ckv_b, "kr": kr_b}
