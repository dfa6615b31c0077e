"""Random weights from the seed, made by the benchmark on the device.

The tree has the layout the system under test takes (``embedding``,
``final_norm``, ``layers`` stacked on a leading layer axis, ``lm_head``).
Each layer is drawn from its own key, ``fold_in(layer_key, i)``, so the
reference can draw one layer at a time and never needs the whole model.

``nmg`` weights are drawn n:m:g-exact: in every chunk of ``C(m, n) * g``
m-blocks along the input axis, shared by ``gr`` output columns, each of the
``C(m, n)`` patterns keeps its ``n`` entries in exactly ``g`` blocks and the
rest is zero.  That is what a model trained under STen's n:m:g mask holds,
and what the serving conversion has to keep without loss.
"""

from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np


#: query and key projections drawn wider than fan-in scale, so that scores
#: spread (a standard deviation of about 2.5 before the softmax) and each
#: position attends to a few others, as in a trained model; at fan-in scale
#: attention is nearly uniform and what a layer reads from its cache hardly
#: shows in the logits
QK_GAIN = {"attn.wq": 1.6, "attn.wk": 1.6}


def model_dims(cfg: dict) -> dict:
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return {"D": cfg["hidden_size"], "L": cfg["num_hidden_layers"],
            "H": cfg["num_attention_heads"],
            "KV": cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            "hd": hd, "F": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def seed_key(seed: int):
    """A key from any whole number (seeds may exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def nmg_mask(key, K: int, N: int, n: int, m: int, g: int, gr: int):
    """Boolean [K, N] n:m:g pattern; K must be a multiple of C(m,n)*g*m and
    N of gr."""
    pats = np.zeros((math.comb(m, n), m), np.float32)
    for i, keep in enumerate(itertools.combinations(range(m), n)):
        pats[i, list(keep)] = 1.0
    C = pats.shape[0]
    CG = C * g
    if K % (CG * m) or N % gr:
        raise ValueError(f"[{K}, {N}] does not tile n:m:g {n}:{m}:{g} "
                         f"gr={gr}")
    groups, chunks = N // gr, K // (CG * m)
    order = jnp.argsort(jax.random.uniform(key, (groups, chunks, CG)), -1)
    pattern = jnp.repeat(jnp.arange(C), g)[order]        # [Gr, nc, CG]
    mask = jnp.asarray(pats)[pattern].reshape(groups, K)  # [Gr, K]
    return jnp.repeat(mask, gr, axis=0).T > 0             # [K, N]


def _normal(key, shape, std):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * std


def make_layer(key, cfg: dict, layout: dict, dtype):
    """One layer's weights.  ``layout`` with ``kind`` "nmg" draws the
    weights it targets n:m:g-exact, at a density-corrected scale."""
    d = model_dims(cfg)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    ks = jax.random.split(key, 10)
    shapes = {"attn.wq": (D, H * hd), "attn.wk": (D, KV * hd),
              "attn.wv": (D, KV * hd), "attn.wo": (H * hd, D),
              "mlp.wi": (D, F), "mlp.wo": (F, D)}
    targets = set(layout["targets"]) if layout["kind"] == "nmg" else set()
    out = {"attn": {}, "mlp": {}}
    for i, (name, (K, N)) in enumerate(shapes.items()):
        kw, km = jax.random.split(ks[i])
        if name in targets:
            n, m = layout["n"], layout["m"]
            w = _normal(kw, (K, N), math.sqrt(m / n / K))
            w = w * nmg_mask(km, K, N, n, m, layout["g"], layout["gr"])
        else:
            w = _normal(kw, (K, N), QK_GAIN.get(name, 1.0) / math.sqrt(K))
        group, leaf = name.split(".")
        out[group][leaf] = w.astype(dtype)
    out["ln1"] = (0.1 * jax.random.normal(ks[6], (D,))).astype(dtype)
    out["ln2"] = (0.1 * jax.random.normal(ks[7], (D,))).astype(dtype)
    return out


def _keys(key):
    return jax.random.split(key, 4)   # embedding, layers, head, norm


def layer_key(key, i: int):
    return jax.random.fold_in(_keys(key)[1], i)


def make_outer(key, cfg: dict, dtype):
    """Embedding, final norm and output head."""
    d = model_dims(cfg)
    k_emb, _, k_head, k_norm = _keys(key)
    return {
        # scaled by sqrt(hidden_size) on lookup: unit scale in the residual
        "embedding": _normal(k_emb, (d["V"], d["D"]),
                             1.0 / math.sqrt(d["D"])).astype(dtype),
        "final_norm": (0.1 * jax.random.normal(k_norm, (d["D"],))
                       ).astype(dtype),
        "lm_head": _normal(k_head, (d["D"], d["V"]),
                           1.0 / math.sqrt(d["D"])).astype(dtype),
    }


def make_params(key, cfg: dict, layout: dict, dtype):
    """The whole tree on the default device: the outer weights in one
    jitted call, then each layer drawn into its slot of the stacked tree
    by one jitted call per layer, the stack donated, so that no more than
    one layer is ever held in float32.  ``key`` is an argument of every
    program, so one compiled program serves every seed."""
    L = model_dims(cfg)["L"]
    params = jax.jit(lambda k: make_outer(k, cfg, dtype))(key)
    one = jax.eval_shape(lambda k: make_layer(k, cfg, layout, dtype), key)
    stack = jax.jit(lambda: jax.tree_util.tree_map(
        lambda s: jnp.zeros((L,) + s.shape, s.dtype), one))()

    def put(stack, key, i):
        layer = make_layer(layer_key(key, i), cfg, layout, dtype)
        return jax.tree_util.tree_map(lambda b, x: b.at[i].set(x), stack,
                                      layer)

    put = jax.jit(put, donate_argnums=0)
    for i in range(L):
        stack = put(stack, key, jnp.int32(i))
    params["layers"] = stack
    return params
