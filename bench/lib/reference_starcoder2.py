"""The plain reference of StarCoder2's block, and its seeded weights.

It imports nothing of the program.  The block follows BigCode's
description (arXiv:2402.19173) and the ``config.json`` of
bigcode/starcoder2-15b: pre-norm residual layers with LayerNorm (weight
and bias, eps ``norm_epsilon``), biased q, k, v and o projections, rotary
positions (theta ``rope_theta``, halves rotated), grouped-query causal
softmax attention (query head ``h`` reads key head ``h // (H / KV)``)
inside a sliding window (key ``j`` is visible to query ``i`` iff
``i - sliding_window < j <= i``), a non-gated MLP c_fc -> tanh GELU ->
c_proj with biases, a final LayerNorm, embeddings not scaled, and an
untied output head.

Products run in float32 at ``highest`` precision; ``precision="fp8"`` is
the control: every projection's operands rounded to float8 (e4m3, one
scale per tensor), one step below the configuration's bfloat16.  The
reference draws one layer's weights at a time and computes attention in
blocks of queries, so a check over a few thousand tokens fits beside
nothing else on one chip.

The weights (:func:`make_layer`, :func:`make_outer`) have the tree the
program takes: the matrices as ``weights.make_layer`` draws them (the
n:m:g targets n:m:g-exact), norm weights ``1 + 0.1 N(0, 1)``, biases and
norm biases ``0.1 N(0, 1)``, embeddings at unit scale in the residual.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from bench.lib import weights as W
from bench.lib.reference import gelu_tanh, matmul, rope

#: query rows of one attention block: [heads, block, S] scores at a time
QUERY_BLOCK = 256

#: the bias leaves of one layer and the extent of each
_BIASES = (("attn", "bq", "q"), ("attn", "bk", "kv"), ("attn", "bv", "kv"),
           ("attn", "bo", "D"), ("mlp", "bi", "F"), ("mlp", "bo", "D"))


def _sizes(cfg: dict) -> dict:
    d = W.model_dims(cfg)
    return {"q": d["H"] * d["hd"], "kv": d["KV"] * d["hd"], "D": d["D"],
            "F": d["F"]}


def _small(key, n: int):
    return 0.1 * jax.random.normal(key, (n,), jnp.float32)


def make_layer(key, cfg: dict, layout: dict, dtype):
    """One layer: the matrices of ``weights.make_layer``, LayerNorm
    weights and biases, and the projections' biases."""
    out = W.make_layer(key, cfg, layout, dtype)
    sizes = _sizes(cfg)
    ks = jax.random.split(jax.random.fold_in(key, 1), len(_BIASES) + 2)
    for k, (group, leaf, size) in zip(ks, _BIASES):
        out[group][leaf] = _small(k, sizes[size]).astype(dtype)
    for k, ln in zip(ks[len(_BIASES):], ("ln1", "ln2")):
        out[ln] = (1.0 + out[ln].astype(jnp.float32)).astype(dtype)
        out[ln + "_b"] = _small(k, sizes["D"]).astype(dtype)
    return out


def make_outer(key, cfg: dict, dtype):
    """Embedding (unit scale: StarCoder2 does not scale it), final
    LayerNorm and output head."""
    d = W.model_dims(cfg)
    k_emb, _, k_head, k_norm = W._keys(key)
    kw, kb = jax.random.split(k_norm)
    return {
        "embedding": W._normal(k_emb, (d["V"], d["D"]), 1.0).astype(dtype),
        "final_norm": (1.0 + _small(kw, d["D"])).astype(dtype),
        "final_norm_b": _small(kb, d["D"]).astype(dtype),
        "lm_head": W._normal(k_head, (d["D"], d["V"]),
                             1.0 / math.sqrt(d["D"])).astype(dtype),
    }


def layer_norm(x, w, b, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def attention(q, k, v, window: int):
    """Causal grouped-query attention inside the window, one block of
    :data:`QUERY_BLOCK` queries at a time.  q [B, S, H, hd], k and v
    [B, S, KV, hd]; S a multiple of the block (or below it)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    blk = min(QUERY_BLOCK, S)
    if S % blk:
        raise ValueError(f"{S} positions are not whole blocks of {blk}")
    kpos = jnp.arange(S)

    def block(args):
        i0, qb = args                               # qb [B, blk, KV, G, hd]
        qpos = i0 + jnp.arange(blk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        seen = (kpos[None, :] <= qpos[:, None]) \
            & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(seen[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    qs = jnp.moveaxis(q.reshape(B, S // blk, blk, KV, G, hd), 1, 0)
    out = jax.lax.map(block, (jnp.arange(0, S, blk), qs))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H * hd)


def layer(lp, x, cfg: dict, precision: str):
    """One pre-norm block over x [B, S, D] (float32)."""
    d = W.model_dims(cfg)
    B, S, _ = x.shape
    H, KV, hd = d["H"], d["KV"], d["hd"]
    eps, theta = cfg["norm_epsilon"], cfg["rope_theta"]
    at, mlp = lp["attn"], lp["mlp"]
    pos = jnp.arange(S)
    h = layer_norm(x, lp["ln1"], lp["ln1_b"], eps)
    q = (matmul(h, at["wq"], precision) + at["bq"]).reshape(B, S, H, hd)
    k = (matmul(h, at["wk"], precision) + at["bk"]).reshape(B, S, KV, hd)
    v = (matmul(h, at["wv"], precision) + at["bv"]).reshape(B, S, KV, hd)
    a = attention(rope(q, pos, theta), rope(k, pos, theta), v,
                  cfg["sliding_window"])
    x = x + matmul(a, at["wo"], precision) + at["bo"]
    h = layer_norm(x, lp["ln2"], lp["ln2_b"], eps)
    f = gelu_tanh(matmul(h, mlp["wi"], precision) + mlp["bi"])
    return x + matmul(f, mlp["wo"], precision) + mlp["bo"]


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str, layout_json: str, dtype_name: str,
              precision: str):
    """The jitted pieces: weights are drawn by programs of their own, so
    no program holds the draw's float32 temporaries beside a layer's
    activations."""
    cfg, layout = json.loads(cfg_json), json.loads(layout_json)
    dtype = jnp.dtype(dtype_name)
    draw_outer = jax.jit(lambda key: make_outer(key, cfg, dtype))
    draw_layer = jax.jit(lambda key, i: make_layer(W.layer_key(key, i), cfg,
                                                   layout, dtype))

    @jax.jit
    def embed(outer, tokens):
        return jnp.take(outer["embedding"], tokens, axis=0).astype(
            jnp.float32)

    @jax.jit
    def block(lp, x):
        return layer(_f32(lp), x, cfg, precision)

    @jax.jit
    def head(outer, x):
        outer = _f32(outer)
        h = layer_norm(x, outer["final_norm"], outer["final_norm_b"],
                       cfg["norm_epsilon"])
        return matmul(h, outer["lm_head"], precision)

    return draw_outer, draw_layer, embed, block, head


def logits(key, cfg: dict, layout, dtype, tokens, precision: str = "f32"):
    """Logits [B, S, V] (float32) of the reference over ``tokens`` [B, S],
    drawing one layer's weights at a time."""
    draw_outer, draw_layer, embed, block, head = _programs(
        json.dumps(cfg, sort_keys=True), json.dumps(layout, sort_keys=True),
        jnp.dtype(dtype).name, precision)
    with jax.default_matmul_precision("highest"):
        outer = draw_outer(key)
        x = embed(outer, tokens)
        for i in range(cfg["num_hidden_layers"]):
            x = block(draw_layer(key, jnp.int32(i)), x)
        return head(outer, x)
