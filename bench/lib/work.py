"""Operations and bytes that a piece of work requires, from its shapes.

The count is the layout's, never the implementation's: an n:m:g weight of
``K x N`` applied to ``M`` rows needs ``2 M K N n/m`` operations and reads
its stored values and block index as the format holds them, plus the
activations in and out.  The one-hot decompression a kernel may do on the
MXU is not counted, so any kernel that does the same job is read against
the same number, and no share of a roofline can pass 100%.
"""

from __future__ import annotations

from bench.lib.weights import model_dims


def nmg_bytes(K: int, N: int, n: int, m: int, g: int, gr: int,
              value_bytes: int = 2) -> int:
    """Stored bytes of an n:m:g ``K x N`` weight: the kept values and the
    int32 index of the original block at each chunk position, one per
    ``gr`` output columns."""
    blocks = K // m
    return N * blocks * n * value_bytes + (N // gr) * blocks * 4


def nmg_matmul(M: int, K: int, N: int, layout: dict,
               act_bytes: int = 2) -> tuple:
    """(operations, bytes) of ``[M, K] @ W[K, N]`` with W in n:m:g."""
    n, m = layout["n"], layout["m"]
    flops = 2 * M * K * N * n / m
    byts = nmg_bytes(K, N, n, m, layout["g"], layout["gr"]) \
        + (M * K + M * N) * act_bytes
    return flops, byts


def least_time(flops: float, byts: float, peaks: dict) -> tuple:
    """(seconds, bound) of the roofline: the larger of operations over peak
    rate and bytes over peak bandwidth, and which of the two it is."""
    tc = flops / peaks["flops_bf16"]
    tm = byts / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def nmg_least_time(M: int, cfg: dict, layout: dict, peaks: dict) -> tuple:
    """Least time of every n:m:g weight of one forward step at ``M`` rows,
    all layers, and the bound that dominates it."""
    d = model_dims(cfg)
    shapes = {"attn.wq": (d["D"], d["H"] * d["hd"]),
              "attn.wk": (d["D"], d["KV"] * d["hd"]),
              "attn.wv": (d["D"], d["KV"] * d["hd"]),
              "attn.wo": (d["H"] * d["hd"], d["D"]),
              "mlp.wi": (d["D"], d["F"]), "mlp.wo": (d["F"], d["D"])}
    total, bounds = 0.0, {"compute": 0.0, "memory": 0.0}
    for name in layout["targets"]:
        K, N = shapes[name]
        t, b = least_time(*nmg_matmul(M, K, N, layout), peaks)
        total += t
        bounds[b] += t
    return total * d["L"], max(bounds, key=bounds.get)


def weight_flops_per_token(cfg: dict, density: dict | None = None,
                           head: bool = True) -> float:
    """Matrix-product operations of one token through every weight, each
    scaled by its density (``density[name]``, 1 when absent); the output
    head when ``head``, the embedding lookup never."""
    d = model_dims(cfg)
    dens = density or {}
    per_layer = {
        "attn.wq": d["D"] * d["H"] * d["hd"],
        "attn.wk": d["D"] * d["KV"] * d["hd"],
        "attn.wv": d["D"] * d["KV"] * d["hd"],
        "attn.wo": d["H"] * d["hd"] * d["D"],
        "mlp.wi": d["D"] * d["F"],
        "mlp.wo": d["F"] * d["D"],
    }
    layer = sum(2 * size * dens.get(name, 1.0)
                for name, size in per_layer.items())
    return d["L"] * layer + (2 * d["D"] * d["V"] if head else 0)


def attention_flops(cfg: dict, context: int) -> float:
    """Score and value products of one query position over ``context``
    keys, all layers (causal work only: no masked positions)."""
    d = model_dims(cfg)
    return d["L"] * 4 * context * d["H"] * d["hd"]


def prefill_flops(cfg: dict, S: int, density: dict | None = None) -> float:
    """Forward operations of a causal prompt of ``S`` tokens; the output
    head runs on the last position only."""
    d = model_dims(cfg)
    attn = attention_flops(cfg, 1) * S * (S + 1) / 2
    head = 2 * d["D"] * d["V"]
    return S * weight_flops_per_token(cfg, density, head=False) + attn + head


def decode_flops(cfg: dict, position: int,
                 density: dict | None = None) -> float:
    """Forward operations of one generated token at ``position``."""
    return weight_flops_per_token(cfg, density) \
        + attention_flops(cfg, position + 1)


def serve_density(layout: dict) -> dict:
    return {name: layout["n"] / layout["m"] for name in layout["targets"]}


__all__ = ["nmg_bytes", "nmg_matmul", "least_time", "nmg_least_time",
           "weight_flops_per_token", "attention_flops", "prefill_flops",
           "decode_flops", "serve_density"]
