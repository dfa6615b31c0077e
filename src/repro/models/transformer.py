"""Unified LM stack covering all ten assigned architectures.

Pure functions over nested-dict params.  Layers are scan-stacked (leading L
dim) to keep HLO size and compile time bounded — required for 512-device AOT
compiles on one CPU.  Families:

  * GQA decoder (qwen / starcoder2 / paligemma text / moonshot / arctic attn)
  * Gemma2: alternating local/global attention (scan over layer *pairs*),
    attention + final-logit softcaps, post-norms
  * MLA (minicpm3) with absorbed-latent decode over the compressed cache
  * MoE FFN (moonshot top-6, arctic top-2 + dense residual)
  * SSD/mamba2 (attention-free) and hymba (parallel attn+SSM heads)
  * enc-dec (whisper backbone; conv frontend is a stub per the assignment —
    ``input_specs`` feeds precomputed frame embeddings; RoPE replaces the
    original sinusoidal/learned positions to keep the stack uniform, noted in
    DESIGN.md)
  * VLM prefix (paligemma: precomputed patch embeddings + prefix-LM mask)

Sparsity (the paper's technique) integrates at every projection through
``_mm``: any weight leaf may be a SparsityLayout (FixedMaskTensor during
sparse training, GroupedNMTensor for sparse serving) and dispatches through
sten; ``tag()`` sites let SparsityBuilder plans sparsify intermediates.

Serving: ``prefill`` runs the parallel forward while *collecting* the decode
cache (per-layer K/V, MLA latents, SSM end-states, cross-attn K/V) through
the layer scan; ``decode_step`` is the one-token path over that cache.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import ops as sten_ops
from repro.core.builder import tag
from repro.core.layouts import SparsityLayout
from repro.dist.sharding import logical_constraint
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import ModelConfig, dense_init

__all__ = [
    "init_lm",
    "forward",
    "loss_fn",
    "logits_of",
    "init_cache",
    "prefill",
    "prefill_into_slot",
    "decode_step",
    "decode_step_buffered",
    "init_append_buffer",
    "commit_append_buffer",
]


from repro.models.common import mm as _mm  # sparse-aware weight apply
from repro.models.common import mm_gated


def _rms(x, w, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _layer_norm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _norm(p, name: str, x, cfg: ModelConfig):
    """The block norm ``p[name]``: RMS (scale ``1 + w``), or for
    ``norm_type="layer"`` LayerNorm with weight ``p[name]`` and bias
    ``p[name + "_b"]``."""
    if cfg.norm_type == "layer":
        return _layer_norm(x, p[name], p[name + "_b"])
    return _rms(x, p[name])


def _init_norm(p: dict, name: str, cfg: ModelConfig) -> None:
    """Identity-initialized parameters of the norm ``name`` into ``p``."""
    if cfg.norm_type == "layer":
        p[name] = jnp.ones((cfg.d_model,), cfg.jdtype)
        p[name + "_b"] = jnp.zeros((cfg.d_model,), cfg.jdtype)
    else:
        p[name] = jnp.zeros((cfg.d_model,), cfg.jdtype)


def _bias(y, b):
    return y if b is None else y + b


def _act(name):
    return jax.nn.silu if name == "silu" else functools.partial(
        jax.nn.gelu, approximate=True
    )


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_mlp(key, cfg: ModelConfig):
    D, F = cfg.d_model, cfg.d_ff
    k1, k2 = jax.random.split(key)
    wi = dense_init(k1, (D, 2 * F if cfg.gated_mlp else F), cfg.jdtype)
    wo = dense_init(k2, (F, D), cfg.jdtype)
    p = {"wi": wi, "wo": wo}
    if cfg.proj_bias:
        p["bi"] = jnp.zeros((F,), cfg.jdtype)
        p["bo"] = jnp.zeros((D,), cfg.jdtype)
    return p


def _init_layer(key, cfg: ModelConfig, cross: bool = False):
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {}
    _init_norm(p, "ln1", cfg)
    _init_norm(p, "ln2", cfg)
    if cfg.attn_type in ("gqa", "hybrid"):
        p["attn"] = attn.init_gqa(ks[0], cfg)
    elif cfg.attn_type == "mla":
        p["attn"] = attn.init_mla(ks[0], cfg)
    if cfg.attn_type in ("none", "hybrid"):
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg)
    if cfg.moe is not None:
        p["moe"] = moe_mod.init_moe(ks[2], cfg)
    elif cfg.attn_type != "none":  # pure-SSM blocks have no separate MLP
        p["mlp"] = _init_mlp(ks[3], cfg)
    if cross:
        p["xattn"] = attn.init_gqa(ks[4], cfg)
        p["lnx"] = jnp.zeros((cfg.d_model,), cfg.jdtype)
    if cfg.post_norms:
        p["post_ln1"] = jnp.zeros((cfg.d_model,), cfg.jdtype)
        p["post_ln2"] = jnp.zeros((cfg.d_model,), cfg.jdtype)
    return p


def init_lm(key, cfg: ModelConfig):
    cfg.validate()
    k_emb, k_layers, k_enc, k_head = jax.random.split(key, 4)
    params: dict[str, Any] = {
        "embedding": dense_init(k_emb, (cfg.vocab, cfg.d_model), cfg.jdtype,
                                scale=1.0),
    }
    _init_norm(params, "final_norm", cfg)
    pair = cfg.layer_pattern == "alt_local_global"
    n_bodies = cfg.n_layers // 2 if pair else cfg.n_layers
    cross = cfg.n_enc_layers > 0

    def one_body(k):
        if pair:
            k1, k2 = jax.random.split(k)
            return {"local": _init_layer(k1, cfg, cross),
                    "global": _init_layer(k2, cfg, cross)}
        return _init_layer(k, cfg, cross)

    params["layers"] = jax.vmap(one_body)(jax.random.split(k_layers, n_bodies))

    if cross:
        params["enc_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, cross=False)
        )(jax.random.split(k_enc, cfg.n_enc_layers))
        params["enc_norm"] = jnp.zeros((cfg.d_model,), cfg.jdtype)

    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab),
                                       cfg.jdtype)
    return params


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------


def _sublayer_attn(lp, x, cfg, *, is_local, prefix_len, causal,
                   enc_out=None, collect=False):
    h = _norm(lp, "ln1", x, cfg)
    aout = jnp.zeros_like(x)
    contrib: dict[str, Any] = {}
    if "attn" in lp:
        if cfg.attn_type == "mla":
            a, ckv, kr = attn.apply_mla(lp["attn"], h, cfg, causal=causal)
            if collect:
                contrib["ckv"] = ckv
                contrib["kr"] = kr.reshape(kr.shape[0], kr.shape[1], -1)
        else:
            a, (k, v) = attn.apply_gqa(lp["attn"], h, cfg, is_local=is_local,
                                       prefix_len=prefix_len, causal=causal)
            if collect:
                contrib["k"], contrib["v"] = k, v
        aout = aout + a
    if "ssm" in lp:
        s_out, s_state = ssm_mod.apply_ssm(lp["ssm"], h, cfg,
                                           return_state=collect)
        aout = aout + s_out
        if collect:
            contrib["ssm_state"] = s_state
        if "attn" in lp:
            aout = aout * 0.5  # hymba: mean of parallel heads
    aout = tag("attn.out", aout)
    if cfg.post_norms:
        aout = _rms(aout, lp["post_ln1"])
    x = x + aout

    if enc_out is not None and "xattn" in lp:
        hx = _rms(x, lp["lnx"])
        xa, (xk, xv) = _cross_attn(lp["xattn"], hx, enc_out, cfg)
        if collect:
            contrib["xk"], contrib["xv"] = xk, xv
        x = x + xa
    return x, contrib


def _cross_attn(p, x, enc_out, cfg):
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (_mm(x, p["wq"])).reshape(B, S, H, hd)
    k = (_mm(enc_out, p["wk"])).reshape(B, -1, KV, hd)
    v = (_mm(enc_out, p["wv"])).reshape(B, -1, KV, hd)
    out = attn.chunked_attention(q, k, v, causal=False,
                                 chunk_q=cfg.attn_chunk_q,
                                 chunk_k=cfg.attn_chunk_k)
    return _mm(out.reshape(B, S, -1), p["wo"]), (k, v)


def _cross_attn_cached(p, x, xk, xv, cfg):
    B, S, D = x.shape
    H, hd = cfg.n_heads, cfg.hd
    q = (_mm(x, p["wq"])).reshape(B, S, H, hd)
    out = attn.chunked_attention(q, xk, xv, causal=False,
                                 chunk_q=cfg.attn_chunk_q,
                                 chunk_k=cfg.attn_chunk_k)
    return _mm(out.reshape(B, S, -1), p["wo"])


def _sublayer_ffn(lp, x, cfg):
    h = _norm(lp, "ln2", x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in lp:
        if cfg.moe.impl == "shmap":
            f, aux = moe_mod.apply_moe_shmap(lp["moe"], h, cfg)
        else:
            f, aux = moe_mod.apply_moe(lp["moe"], h, cfg)
    elif "mlp" in lp:
        mlp = lp["mlp"]
        inline = None
        if cfg.mlp_inline_threshold is not None:
            from repro.core.sparsifiers import ScalarThresholdSparsifier
            inline = ScalarThresholdSparsifier(cfg.mlp_inline_threshold)
        if cfg.gated_mlp:
            # fused gated megakernel: projection + split + act + gate in
            # one decode launch when eligible; None -> sequential path
            # (bitwise-equal — the kernel epilogue replays these exact ops)
            hh = mm_gated(h, mlp["wi"], cfg.act, inline=inline)
            if hh is None:
                hh = _mm(h, mlp["wi"], inline=inline)
                u, v = jnp.split(hh, 2, axis=-1)
                hh = _act(cfg.act)(u) * v
        else:
            hh = _bias(_mm(h, mlp["wi"], inline=inline), mlp.get("bi"))
            hh = _act(cfg.act)(hh)
        hh = tag("mlp.act", hh)
        f = _bias(_mm(hh, mlp["wo"]), mlp.get("bo"))
    else:
        return x, aux
    f = tag("mlp.out", f)
    if cfg.post_norms:
        f = _rms(f, lp["post_ln2"])
    return x + f, aux


def _scope(name: str, on: bool):
    return jax.named_scope(name) if on else contextlib.nullcontext()


def _layer(lp, x, cfg, *, is_local, prefix_len, causal, enc_out=None,
           collect=False):
    """One block; a cache-collecting (prefill) forward names its two
    sublayers ``repro.prefill.attn`` and ``repro.prefill.mlp``."""
    with _scope("repro.prefill.attn", collect):
        x, contrib = _sublayer_attn(lp, x, cfg, is_local=is_local,
                                    prefix_len=prefix_len, causal=causal,
                                    enc_out=enc_out, collect=collect)
    with _scope("repro.prefill.mlp", collect):
        x, aux = _sublayer_ffn(lp, x, cfg)
    return x, aux, contrib


def _body_fn(cfg, prefix_len, causal, enc_out=None, collect=False):
    pair = cfg.layer_pattern == "alt_local_global"
    all_local = cfg.layer_pattern == "local"

    def body(carry, lp):
        x, aux = carry
        if pair:
            x, a1, c1 = _layer(lp["local"], x, cfg, is_local=True,
                               prefix_len=prefix_len, causal=causal,
                               enc_out=enc_out, collect=collect)
            x, a2, c2 = _layer(lp["global"], x, cfg, is_local=False,
                               prefix_len=prefix_len, causal=causal,
                               enc_out=enc_out, collect=collect)
            return (x, aux + a1 + a2), {"local": c1, "global": c2}
        x, da, c = _layer(lp, x, cfg, is_local=all_local,
                          prefix_len=prefix_len, causal=causal,
                          enc_out=enc_out, collect=collect)
        return (x, aux + da), c

    return body


def _run_encoder(params, cfg, enc_embeds, dtype, remat="none"):
    e = logical_constraint(enc_embeds.astype(dtype), ("batch", "seq", None))
    enc_body = _body_fn(cfg, 0, causal=False)
    if remat != "none":
        enc_body = jax.checkpoint(enc_body)
    (e, _), _ = jax.lax.scan(enc_body, (e, jnp.zeros((), jnp.float32)),
                             params["enc_layers"])
    return _rms(e, params["enc_norm"])


def _embed(params, cfg: ModelConfig, tokens):
    x = jnp.take(params["embedding"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(jnp.sqrt(1.0 * cfg.d_model), x.dtype)
    return x


def forward(params, cfg: ModelConfig, tokens=None, *, embeds=None,
            enc_embeds=None, prefix_embeds=None, remat: str = "full",
            collect_cache: bool = False):
    """Returns (hidden [B, S, D], moe_aux[, cache_contribs]).

    ``tokens`` [B, S] int32 or ``embeds`` [B, S, D]; ``prefix_embeds`` (VLM)
    are prepended; ``enc_embeds`` (enc-dec) run through the encoder for
    cross-attention.  With ``collect_cache`` the per-layer decode-cache
    contributions are returned stacked on a leading layer axis."""
    if embeds is None:
        embeds = _embed(params, cfg, tokens)
    prefix_len = 0
    if prefix_embeds is not None:
        embeds = jnp.concatenate([prefix_embeds.astype(embeds.dtype), embeds],
                                 axis=1)
        prefix_len = prefix_embeds.shape[1]
    x = logical_constraint(embeds, ("batch", "seq", None))

    enc_out = None
    if cfg.n_enc_layers > 0:
        assert enc_embeds is not None, "enc-dec model needs encoder inputs"
        enc_out = _run_encoder(params, cfg, enc_embeds, x.dtype, remat)

    body = _body_fn(cfg, prefix_len, causal=True, enc_out=enc_out,
                    collect=collect_cache)
    if remat != "none":
        body = jax.checkpoint(body)
    (x, aux), contribs = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["layers"]
    )
    x = _norm(params, "final_norm", x, cfg)
    if collect_cache:
        return x, aux, contribs, enc_out
    return x, aux


def logits_of(params, cfg: ModelConfig, hidden):
    head = params.get("lm_head", None)
    if head is None:
        logits = hidden @ params["embedding"].T
    else:
        logits = _mm(hidden, head)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def loss_fn(params, cfg: ModelConfig, batch, *, remat: str = "full",
            aux_weight: float = 0.01):
    """batch: {'tokens' [B,S], 'labels' [B,S], optional 'enc_embeds',
    'prefix_embeds'}.  Labels < 0 are masked out."""
    hidden, aux = forward(
        params, cfg, batch["tokens"],
        enc_embeds=batch.get("enc_embeds"),
        prefix_embeds=batch.get("prefix_embeds"),
        remat=remat,
    )
    labels = batch["labels"]
    if batch.get("prefix_embeds") is not None:
        hidden = hidden[:, batch["prefix_embeds"].shape[1]:]
    logits = logits_of(params, cfg, hidden).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = (labels >= 0).astype(jnp.float32)
    ll = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + aux_weight * aux, {"ce": loss, "moe_aux": aux}


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


#: static symmetric scale for int8 KV caches (RoPE'd keys/values are O(1);
#: production would track per-head scales — documented simplification)
KV_QUANT_SCALE = 1.0 / 24.0


def _cache_dt(cfg: ModelConfig):
    return jnp.dtype(cfg.kv_cache_dtype) if cfg.kv_cache_dtype else cfg.jdtype


def _q_cache(x, cfg: ModelConfig):
    """Quantize a K/V tile for storage when the cache is int8."""
    if cfg.kv_cache_dtype == "int8":
        return jnp.clip(
            jnp.round(x.astype(jnp.float32) / KV_QUANT_SCALE), -127, 127
        ).astype(jnp.int8)
    return x.astype(_cache_dt(cfg))


def _dq_cache(x, cfg: ModelConfig):
    if x.dtype == jnp.int8:
        return x.astype(cfg.jdtype) * jnp.asarray(KV_QUANT_SCALE, cfg.jdtype)
    return x


def _layer_cache(cfg: ModelConfig, B: int, S: int, enc_len: int = 0):
    c: dict[str, Any] = {}
    cdt = _cache_dt(cfg)
    if cfg.attn_type in ("gqa", "hybrid"):
        kv, hd = cfg.n_kv_heads, cfg.hd
        c["k"] = jnp.zeros((B, S, kv, hd), cdt)
        c["v"] = jnp.zeros((B, S, kv, hd), cdt)
    elif cfg.attn_type == "mla":
        c["ckv"] = jnp.zeros((B, S, cfg.mla.kv_lora_rank), cdt)
        c["kr"] = jnp.zeros((B, S, cfg.mla.qk_rope_head_dim), cdt)
    if cfg.attn_type in ("none", "hybrid"):
        c["ssm_state"] = ssm_mod.init_ssm_state(cfg, B)
    if enc_len and cfg.n_enc_layers > 0:
        kv, hd = cfg.n_kv_heads, cfg.hd
        c["xk"] = jnp.zeros((B, enc_len, kv, hd), cfg.jdtype)
        c["xv"] = jnp.zeros((B, enc_len, kv, hd), cfg.jdtype)
    return c


def init_cache(cfg: ModelConfig, B: int, S: int, *, enc_len: int = 0,
               local_window_cache: bool = True):
    """Stacked per-layer decode cache.  For alt local/global models the
    local layers' KV cache is a ring buffer truncated to the sliding window
    (the gemma2 long-context memory saver)."""
    pair = cfg.layer_pattern == "alt_local_global"
    n_bodies = cfg.n_layers // 2 if pair else cfg.n_layers

    def stack(make):
        one = make()
        return jax.tree_util.tree_map(
            lambda l: jnp.zeros((n_bodies,) + l.shape, l.dtype), one
        )

    if pair:
        S_local = min(S, cfg.local_window) if (
            local_window_cache and cfg.local_window) else S
        return {
            "local": stack(lambda: _layer_cache(cfg, B, S_local, enc_len)),
            "global": stack(lambda: _layer_cache(cfg, B, S, enc_len)),
        }
    return stack(lambda: _layer_cache(cfg, B, S, enc_len))


def _decode_layer(lp, x, cfg, hist, buf, start, pos, *, is_local):
    """One layer of a decode step: ``hist`` is the layer's read-only cache,
    ``buf`` its append buffer (sequence leaves) and carried state (state
    leaves); returns (x, updated buf).  The MLP is scoped
    ``repro.decode.mlp``."""
    h = _norm(lp, "ln1", x, cfg)
    aout = jnp.zeros_like(x)
    new_buf = dict(buf)
    if "attn" in lp:
        if cfg.attn_type == "mla":
            a, upd = attn.decode_mla(
                lp["attn"], h, cfg,
                {"ckv": hist["ckv"], "kr": hist["kr"]},
                {"ckv": buf["ckv"], "kr": buf["kr"]}, start, pos,
                q_cache=_q_cache if cfg.kv_cache_dtype else None,
                dq_cache=(lambda z: _dq_cache(z, cfg))
                if cfg.kv_cache_dtype else None)
        else:
            a, upd = _decode_gqa_at(lp["attn"], h, cfg, hist, buf, start,
                                    pos, is_local=is_local)
        new_buf.update(upd)
        aout = aout + a
    if "ssm" in lp:
        s_out, s_state = ssm_mod.decode_ssm(lp["ssm"], h, cfg,
                                            buf["ssm_state"])
        new_buf["ssm_state"] = s_state
        aout = aout + s_out
        if "attn" in lp:
            aout = aout * 0.5
    if cfg.post_norms:
        aout = _rms(aout, lp["post_ln1"])
    x = x + aout

    if "xattn" in lp and "xk" in buf:
        hx = _rms(x, lp["lnx"])
        x = x + _cross_attn_cached(lp["xattn"], hx, buf["xk"], buf["xv"],
                                   cfg)

    with jax.named_scope("repro.decode.mlp"):
        x, _ = _sublayer_ffn(lp, x, cfg)
    return x, new_buf


def _is_ring(cfg, is_local, S_c) -> bool:
    """A local layer whose cache is no longer than the window is a ring
    buffer: position p lives at row p % S_c."""
    return bool(is_local and cfg.local_window and S_c <= cfg.local_window)


def _decode_gqa_at(p, x, cfg, hist, buf, start, pos, *, is_local):
    """GQA decode; local layers with a window-sized cache use it as a ring
    buffer (position p at row p % S_cache).  ``pos`` and ``start`` are
    per-batch [B] vectors — slots in a continuous batch each write/attend
    at their own position.  The new K/V row goes into the append buffer;
    attention reads a copy of the layer's history with the buffer's
    written rows scattered in (:func:`attention.read_appended`)."""
    B = x.shape[0]
    q, k, v = attn._qkv(p, x, cfg, pos[:, None])
    S_c = hist["k"].shape[1]
    ring = _is_ring(cfg, is_local, S_c)
    with jax.named_scope("kv.write"):
        kb = attn.append_row(buf["k"], _q_cache(k[:, 0], cfg), start, pos)
        vb = attn.append_row(buf["v"], _q_cache(v[:, 0], cfg), start, pos)
        kc = attn.read_appended(hist["k"], kb, start, pos, ring=ring)
        vc = attn.read_appended(hist["v"], vb, start, pos, ring=ring)
    with jax.named_scope("attn.decode"):
        kd, vd = _dq_cache(kc, cfg), _dq_cache(vc, cfg)
        if ring:
            n_valid = jnp.minimum(pos + 1, S_c)
            out = attn.decode_attention(q, kd, vd, n_valid,
                                        softcap=cfg.attn_softcap)
        else:
            window = cfg.local_window if is_local else None
            out = attn.decode_attention(q, kd, vd, pos + 1,
                                        softcap=cfg.attn_softcap,
                                        window=window)
    return attn.out_proj(p, out.reshape(B, 1, -1)), {"k": kb, "v": vb}


def _cache_kinds(cfg: ModelConfig, cache):
    """:func:`_seq_leaf_kinds` for ``cache``, at the encoder length its
    cross K/V leaves (if any) were built for."""
    layer = cache["global"] if cfg.layer_pattern == "alt_local_global" \
        else cache
    return _seq_leaf_kinds(cfg, layer["xk"].shape[2] if "xk" in layer else 0)


def init_append_buffer(cfg: ModelConfig, cache, n_steps: int):
    """The append buffer for ``n_steps`` decode steps over ``cache``: each
    sequence leaf [L, B, S, ...] becomes zeros [L, B, n_steps, ...] (row t
    is position start + t); state leaves (SSM states, cross K/V) are the
    cache's own, carried and updated step by step."""
    return jax.tree_util.tree_map(
        lambda l, is_seq: jnp.zeros(l.shape[:2] + (n_steps,) + l.shape[3:],
                                    l.dtype) if is_seq else l,
        cache, _cache_kinds(cfg, cache))


def _commit_leaf(dst, rows, start, ring):
    """Write rows [L, B, T, ...] of positions start .. start + T - 1 into
    dst [L, B, S, ...], as :func:`attention.appended_rows` places them
    after the chunk's last step."""
    B, S, T = dst.shape[1], dst.shape[2], rows.shape[2]
    w = attn.appended_rows(start, start + T - 1, T, S, ring=ring)
    return dst.at[:, jnp.arange(B)[:, None], w].set(rows)


@jax.named_scope("kv.commit")
def commit_append_buffer(cfg: ModelConfig, cache, buf, start):
    """Write a decode chunk's append buffer into the slot cache: each
    slot's T rows at positions ``start .. start + T - 1`` (ring leaves at
    p % S), and the carried state leaves wholesale."""
    kinds = _cache_kinds(cfg, cache)
    start = attn.pos_vec(start, jax.tree_util.tree_leaves(cache)[0].shape[1])

    def commit(c, b, k, is_local):
        return jax.tree_util.tree_map(
            lambda cl, bl, is_seq: _commit_leaf(
                cl, bl, start, _is_ring(cfg, is_local, cl.shape[2]))
            if is_seq else bl, c, b, k)

    if cfg.layer_pattern == "alt_local_global":
        return {name: commit(cache[name], buf[name], kinds[name],
                             name == "local")
                for name in ("local", "global")}
    return commit(cache, buf, kinds, cfg.layer_pattern == "local")


@jax.named_scope("decode.step")
def decode_step_buffered(params, cfg: ModelConfig, token, history, buf,
                         start, pos):
    """One decode step over a read-only ``history`` cache and an append
    buffer ``buf`` (:func:`init_append_buffer`) that holds the rows of
    positions ``start .. pos - 1`` written by earlier steps of the chunk.
    token [B, 1] int32; start, pos [] or [B] int32.  Returns (logits
    [B, V], buf with this step's rows at ``pos - start`` and the new state
    leaves).  The history is only read: the layer scan slices it, and its
    only cache output is the updated buffer.

    Named scopes tag its device ops for a profiler trace: ``decode.step``
    the embedding, final norm and logits, ``decode.layers`` the layer
    scan's slicing of the stacked history and buffer and restacking of
    the buffer, ``decode.layer`` each layer's body."""
    x = logical_constraint(_embed(params, cfg, token), ("batch", None, None))
    B = token.shape[0]
    pos, start = attn.pos_vec(pos, B), attn.pos_vec(start, B)
    pair = cfg.layer_pattern == "alt_local_global"
    all_local = cfg.layer_pattern == "local"

    @jax.named_scope("decode.layer")
    def body(h, xs):
        lp, hc, bc = xs
        if pair:
            h, bl = _decode_layer(lp["local"], h, cfg, hc["local"],
                                  bc["local"], start, pos, is_local=True)
            h, bg = _decode_layer(lp["global"], h, cfg, hc["global"],
                                  bc["global"], start, pos, is_local=False)
            return h, {"local": bl, "global": bg}
        return _decode_layer(lp, h, cfg, hc, bc, start, pos,
                             is_local=all_local)

    with jax.named_scope("decode.layers"):
        x, buf = jax.lax.scan(body, x, (params["layers"], history, buf))
    x = _norm(params, "final_norm", x, cfg)
    logits = logits_of(params, cfg, x)[:, 0]
    return logits, buf


def decode_step(params, cfg: ModelConfig, token, cache, pos):
    """token [B, 1] int32; pos [] or [B] int32 (per-slot positions for the
    continuous-batching engine); returns (logits [B, V], new cache): one
    :func:`decode_step_buffered` step with a one-row buffer, then that row
    committed at ``pos``."""
    pos = attn.pos_vec(pos, token.shape[0])
    logits, buf = decode_step_buffered(
        params, cfg, token, cache, init_append_buffer(cfg, cache, 1), pos,
        pos)
    return logits, commit_append_buffer(cfg, cache, buf, pos)


def _to_cache_dtype(piece, dst_dtype):
    """Cast a collected contribution to the cache dtype, quantizing when the
    cache is int8."""
    if dst_dtype == jnp.int8 and piece.dtype != jnp.int8:
        piece = jnp.clip(
            jnp.round(piece.astype(jnp.float32) / KV_QUANT_SCALE), -127, 127)
    return piece.astype(dst_dtype)


@functools.lru_cache(maxsize=None)
def _seq_leaf_kinds(cfg: ModelConfig, enc_len: int):
    """Which cache leaves carry a sequence axis: probe ``init_cache`` at
    two lengths (shape-only, via eval_shape) and mark the leaves whose
    shape varies.  K/V, MLA latents vary; SSM conv/ssd states and cross
    K/V (sized by enc_len) do not.  Probe lengths are tiny so even ring
    (window-clamped) leaves are classified as sequence leaves."""
    probe = lambda s: jax.eval_shape(  # noqa: E731
        lambda: init_cache(cfg, 1, s, enc_len=enc_len)
    )
    return jax.tree_util.tree_map(
        lambda a, b: a.shape != b.shape, probe(2), probe(3)
    )


def _write_slot_leaf(dst, src, slot, offset, is_seq):
    """Write one request's collected cache leaf into batch row ``slot``.

    dst [L, B_slots, ...] is a serving cache leaf; src [L, 1, ...] the
    corresponding prefill contribution.  Sequence leaves (K/V, MLA
    latents) gain a seq axis in dst: the row for absolute position p is
    ``p % S_cache``, so ring (sliding-window) caches stay aligned with the
    decode path's ``pos % S_cache`` writes for *any* prompt length, and
    full-size caches (S_cache >= offset + S) get the identity placement.
    State leaves (SSM conv/ssd states, cross K/V) are overwritten
    wholesale; ``is_seq`` comes from :func:`_seq_leaf_kinds`, not shape
    coincidence, so a prompt that exactly fills the cache still honors
    ``offset``."""
    src = src[:, 0]  # [L, ...]
    if not is_seq:  # state leaf
        assert dst.shape[2:] == src.shape[1:], (dst.shape, src.shape)
        return dst.at[:, slot].set(_to_cache_dtype(src, dst.dtype))
    assert dst.ndim == src.ndim + 1 and dst.shape[3:] == src.shape[2:], (
        dst.shape, src.shape)
    S_c, S_src = dst.shape[2], src.shape[1]
    take = min(S_src, S_c)  # ring caches keep the tail
    piece = _to_cache_dtype(src[:, -take:], dst.dtype)
    rows = (jnp.asarray(offset) + (S_src - take)
            + jnp.arange(take, dtype=jnp.int32)) % S_c
    return dst.at[:, slot, rows].set(piece)


def prefill(params, cfg: ModelConfig, tokens, cache_len: int | None = None, *,
            enc_embeds=None, prefix_embeds=None, cache=None, slot=None,
            write_offset=0):
    """Parallel forward that also materializes the decode cache.

    Returns (last-position logits [B, V], cache).  Two modes:

    * ``cache_len`` given (classic): allocates a fresh ``cache_len``-sized
      cache and writes the collected per-layer K/V (and MLA latents / SSM
      end-states / cross K-V) at positions [0, S) for the whole batch.
    * ``cache`` + ``slot`` given (serving): ``tokens`` is a single request
      [1, S] and the contributions are written *into* the existing
      static-shape slot cache at batch row ``slot``, seq offset
      ``write_offset`` — the continuous-batching admission path.  ``slot``
      and ``write_offset`` may be traced, so one compiled prefill serves
      every slot.  NOTE: the contributions carry RoPE phases computed from
      position 0 and the forward pass does not read the existing cache, so
      a nonzero ``write_offset`` only *places* rows — prefix-continuation
      prefill (RoPE offset + attention over cached prefix rows) is not yet
      implemented; the engine always admits at offset 0.
    """
    B, S = tokens.shape
    hidden, _, contribs, enc_out = forward(
        params, cfg, tokens, enc_embeds=enc_embeds,
        prefix_embeds=prefix_embeds, remat="none", collect_cache=True,
    )
    logits = logits_of(params, cfg, hidden[:, -1:])[:, 0]

    if cache is not None:
        assert slot is not None, "slot-mode prefill needs a slot index"
        assert B == 1, "slot-mode prefill admits one request at a time"
        kinds = _seq_leaf_kinds(
            cfg, enc_embeds.shape[1] if enc_embeds is not None else 0
        )
        cache = jax.tree_util.tree_map(
            lambda d, s, isq: _write_slot_leaf(d, s, slot, write_offset,
                                               isq),
            cache, contribs, kinds,
        )
        return logits, cache

    assert cache_len is not None, "prefill needs cache_len or cache+slot"
    enc_len = enc_embeds.shape[1] if enc_embeds is not None else 0
    cache = init_cache(cfg, B, cache_len, enc_len=enc_len)

    def place(dst, src):
        # dst [L, B, S_cache, ...] vs src [L, B, S_seen, ...]: leaves differ
        # only on the seq axis (2).  Ring (window) caches keep the last
        # S_cache entries; ring write positions assume S % S_cache == 0
        # (holds for the assigned shapes: 32768/524288 vs window 4096).
        if dst.shape == src.shape:
            return _to_cache_dtype(src, dst.dtype)
        assert (dst.ndim == src.ndim and dst.shape[:2] == src.shape[:2]
                and dst.shape[3:] == src.shape[3:]), (dst.shape, src.shape)
        take = min(src.shape[2], dst.shape[2])
        piece = src[:, :, -take:]
        return jax.lax.dynamic_update_slice(
            dst, _to_cache_dtype(piece, dst.dtype), (0,) * dst.ndim
        )

    cache = jax.tree_util.tree_map(place, cache, contribs)
    return logits, cache


def prefill_into_slot(params, cfg: ModelConfig, tokens, cache, slot, *,
                      write_offset=0, enc_embeds=None, prefix_embeds=None):
    """Admit one request into a serving cache: prefill ``tokens`` [1, S] and
    write its cache contributions into batch row ``slot`` at
    ``write_offset``.  Returns (last-position logits [1, V], cache)."""
    return prefill(params, cfg, tokens, enc_embeds=enc_embeds,
                   prefix_embeds=prefix_embeds, cache=cache, slot=slot,
                   write_offset=write_offset)
