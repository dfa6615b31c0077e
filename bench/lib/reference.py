"""The plain reference: the configuration's forward pass in ``jax.numpy``.

It imports nothing of the program.  Weights come from the benchmark's own
generator (``weights.py``), drawn again from the seed one layer at a time,
so the reference never holds the whole model.  Matrix products run in
float32 at ``highest`` precision; ``precision="fp8"`` is the control: every
projection's operands rounded to float8 (e4m3, one scale per tensor), the
step below the configuration's bfloat16 that would tempt a faster program.

The block is the one the configuration file states: pre-norm residual
layers with RMS norm scaled by ``1 + w``, rotary positions on q and k
(halves rotated), grouped-query causal softmax attention (query head ``h``
reads key head ``h // (H / KV)``), a tanh-approximated GELU MLP without a
gate, no biases, embeddings scaled by ``sqrt(hidden_size)``, and an untied
output head.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from bench.lib import weights as W

F8_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 with one scale for the tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(x, w, precision: str):
    if precision == "fp8":
        x, w = _q8(x), _q8(w)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def rms(x, w):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * (1.0 + w)


def rope(x, positions, theta: float):
    """x [B, S, heads, hd]; positions [S]."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[:, None].astype(jnp.float32) * freqs      # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def layer(lp, x, cfg: dict, precision: str):
    """One pre-norm block over x [B, S, D] (float32)."""
    d = W.model_dims(cfg)
    B, S, _ = x.shape
    H, KV, hd = d["H"], d["KV"], d["hd"]
    pos = jnp.arange(S)
    h = rms(x, lp["ln1"])
    q = matmul(h, lp["attn"]["wq"], precision).reshape(B, S, H, hd)
    k = matmul(h, lp["attn"]["wk"], precision).reshape(B, S, KV, hd)
    v = matmul(h, lp["attn"]["wv"], precision).reshape(B, S, KV, hd)
    q = rope(q, pos, cfg["rope_theta"])
    k = rope(k, pos, cfg["rope_theta"])
    G = H // KV
    causal = pos[None, :] <= pos[:, None]

    def group(args):
        # one key head and the G query heads that read it, so that only
        # [B, G, S, S] scores are held at a time
        qg, kg, vg = args                 # [B, S, G, hd], [B, S, hd] x 2
        s = jnp.einsum("bqgd,bkd->bgqk", qg, kg,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", p, vg,
                          precision=jax.lax.Precision.HIGHEST)

    qs = jnp.moveaxis(q.reshape(B, S, KV, G, hd), 2, 0)
    a = jax.lax.map(group, (qs, jnp.moveaxis(k, 2, 0),
                            jnp.moveaxis(v, 2, 0)))   # [KV, B, S, G, hd]
    a = jnp.moveaxis(a, 0, 2).reshape(B, S, H * hd)
    x = x + matmul(a, lp["attn"]["wo"], precision)
    h = rms(x, lp["ln2"])
    f = gelu_tanh(matmul(h, lp["mlp"]["wi"], precision))
    return x + matmul(f, lp["mlp"]["wo"], precision)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str, layout_json: str, dtype_name: str,
              precision: str):
    cfg, layout = json.loads(cfg_json), json.loads(layout_json)
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def embed(key, tokens):
        outer = _f32(W.make_outer(key, cfg, dtype))
        x = jnp.take(outer["embedding"], tokens, axis=0)
        return x * math.sqrt(cfg["hidden_size"])

    @jax.jit
    def block(key, i, x):
        lp = _f32(W.make_layer(W.layer_key(key, i), cfg, layout, dtype))
        return layer(lp, x, cfg, precision)

    @jax.jit
    def head(key, x):
        outer = _f32(W.make_outer(key, cfg, dtype))
        h = rms(x, outer["final_norm"])
        return matmul(h, outer["lm_head"], precision)

    return embed, block, head


def logits(key, cfg: dict, layout, dtype, tokens, precision: str = "f32"):
    """Logits [B, S, V] (float32) of the reference over ``tokens`` [B, S],
    drawing one layer's weights at a time."""
    embed, block, head = _programs(json.dumps(cfg, sort_keys=True),
                                   json.dumps(layout, sort_keys=True),
                                   jnp.dtype(dtype).name, precision)
    x = embed(key, tokens)
    for i in range(cfg["num_hidden_layers"]):
        x = block(key, jnp.int32(i), x)
    return head(key, x)
