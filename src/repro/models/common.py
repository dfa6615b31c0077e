"""Model configuration dataclasses and parameter-initialization utilities.

One unified config covers all ten assigned architectures (dense GQA, MLA,
local/global alternation + softcap, QKV bias, MoE w/ optional dense residual,
SSM/SSD, hybrid attn+SSM, enc-dec, VLM-prefix).  Models are pure functions
over nested-dict param pytrees; sharding is decided *outside* the model by
path-based rules (dist/sharding.py), keeping model code mesh-agnostic.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["MoEConfig", "MLAConfig", "SSMConfig", "ModelConfig", "dense_init",
           "mm", "mm_fused_qkv", "mm_gated"]


def mm(x, w, *, inline=None):
    """Weight application admitting sparse layouts (the paper's technique
    integrates here: FixedMaskTensor during masked training, GroupedNMTensor
    for sparse serving — dispatched through the sten registry, so the same
    registered kernels back training forwards and serving).

    ``inline`` (optional) is a streaming sparsifier fused into the matmul
    when a fused implementation is registered (paper §3.3 — e.g.
    ``ScalarThresholdSparsifier`` hits ``matmul_threshold_pallas``); the
    produced intermediate is returned masked-dense so surrounding model code
    stays dense.
    """
    from repro.core.layouts import DenseTensor, SparsityLayout

    if not isinstance(w, SparsityLayout) and inline is None:
        return x @ w

    from repro.core import ops as sten_ops

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if isinstance(w, SparsityLayout):
        # layout signature dispatch: FixedMask -> masked matmul impl,
        # GroupedNM -> the shape-routed nmg kernels (decode-shaped x hits
        # the GEMV path, prefill-shaped x the SpMM path) — the weight is
        # never densified here; only registered impls decide its
        # representation
        y = sten_ops.linear(x2, w, inline=inline)
    else:
        # dense weight + inline sparsifier: wrap operands so dispatch sees
        # DenseTensor signatures and can pick the fused kernel
        y = sten_ops.matmul(DenseTensor(x2), DenseTensor(w), inline=inline)
    if isinstance(y, SparsityLayout):
        y = y.to_dense()
    # match the dense path's promotion semantics (x @ w), so sparsifying a
    # weight never changes a layer's output dtype; the decode GEMV kernel
    # already emits x.dtype, in which case this cast is a no-op
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if y.dtype != out_dtype:
        y = y.astype(out_dtype)
    return y.reshape(*lead, -1)


def mm_fused_qkv(x, wq, wk, wv):
    """The attention projections, through the decode megakernel when
    eligible: one launch computes q/k/v instead of one per projection.
    Ineligible groups (dense weights, mixed formats,
    prefill-shaped x, table veto) fall back to three :func:`mm` calls;
    outputs are bitwise-equal either way, so this is purely a launch-count
    optimization."""
    from repro.kernels import ops as kops

    ys = kops.maybe_fused_qkv(x, (wq, wk, wv))
    ws = (wq, wk, wv)
    if ys is None:
        return tuple(mm(x, w) for w in ws)
    # the fused route emits x.dtype (like the per-projection decode
    # kernel); apply mm()'s promotion semantics on top so fusing never
    # changes a layer's output dtype
    outs = []
    for y, w in zip(ys, ws):
        out_dtype = jnp.result_type(x.dtype, w.dtype)
        outs.append(y.astype(out_dtype) if y.dtype != out_dtype else y)
    return tuple(outs)


def mm_gated(x, w, act: str, *, inline=None):
    """The gated-MLP pair (packed [D, 2F] weight) with the activation fused
    into the projection's kernel epilogue, or **None** when the megakernel
    route is ineligible — the caller then runs the sequential
    projection/split/activation path.  Only fires when no promotion cast
    would sit between projection and gate (promotion would change where the
    activation's rounding happens, breaking fused ≡ sequential bitwise)."""
    if inline is not None:
        return None
    if jnp.result_type(x.dtype, getattr(w, "dtype", x.dtype)) != x.dtype:
        return None
    from repro.kernels import ops as kops

    return kops.maybe_fused_ffn(x, w, act=act)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    d_expert: int = 1024          # expert FFN hidden size
    capacity_factor: float = 1.25
    dense_residual: bool = False  # Arctic-style dense MLP in parallel
    dense_residual_ff: int = 0
    router_jitter: float = 0.0
    combine: str = "gather"   # gather | scatter (EP combine strategy)
    impl: str = "pjit"        # pjit | shmap (explicit shard_map EP)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 768
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256
    acc_dtype: str = "float32"   # SSD intra-chunk einsum dtype (hillclimb)

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def num_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    vocab: int = 32000
    d_model: int = 1024
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: int = 8
    head_dim: int = 0             # 0 -> d_model // n_heads
    d_ff: int = 4096
    # attention family
    attn_type: str = "gqa"        # gqa | mla | none (pure SSM) | hybrid
    qkv_bias: bool = False        # Qwen-style
    proj_bias: bool = False       # biases on attn.wo, mlp.wi, mlp.wo (non-gated)
    logit_softcap: Optional[float] = None      # Gemma2 final-logit softcap
    attn_softcap: Optional[float] = None       # Gemma2 attention softcap
    local_window: Optional[int] = None         # sliding-window size
    layer_pattern: str = "global"  # global | local | alt_local_global
    post_norms: bool = False       # Gemma2 pre+post block norms
    # rms: x / rms(x) * (1 + w), eps 1e-6;  layer: LayerNorm with weight
    # and bias (x - mean) / std * w + b, eps 1e-5 (StarCoder2)
    norm_type: str = "rms"
    act: str = "silu"              # silu | gelu
    gated_mlp: bool = True
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    embed_scale: bool = True       # embeddings times sqrt(d_model)
    # sub-family configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    # enc-dec (whisper): n_enc_layers > 0 enables the encoder + cross-attn
    n_enc_layers: int = 0
    # VLM: number of (precomputed, stub-frontend) prefix embeddings
    vision_prefix: int = 0
    # execution knobs (perf hillclimb surface)
    attn_chunk_q: int = 512   # attention tile sizes: smaller tiles keep
    attn_chunk_k: int = 512   # score blocks VMEM-resident (flash-style)
    attn_dtype: str = "float32"  # streamed Q/K/V dtype (bf16 halves traffic;
    #                              softmax stats/accumulator stay f32)
    kv_cache_dtype: Optional[str] = None  # e.g. "int8" (quantized KV cache)
    # numerics
    dtype: str = "bfloat16"
    # paper integration: which weights the sparsity plan targets by default
    sparse_targets: tuple = ("mlp.wi", "mlp.wo", "attn.wo")
    # fused inline sparsifier (paper §3.3): when set, the MLP up-projection
    # runs through the fused matmul+threshold kernel and the produced
    # intermediate is thresholded in-stream (kernels/fused_sparse_matmul.py)
    mlp_inline_threshold: Optional[float] = None

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def validate(self):
        assert self.n_heads % max(1, self.n_kv_heads) == 0
        assert self.norm_type in ("rms", "layer")
        assert not (self.proj_bias and self.gated_mlp), \
            "MLP biases are implemented for the non-gated MLP only"
        if self.attn_type == "mla":
            assert self.mla is not None
        if self.attn_type in ("none", "hybrid"):
            assert self.ssm is not None
        if self.layer_pattern == "alt_local_global":
            assert self.n_layers % 2 == 0 and self.local_window
        return self

    def scaled(self, **kw) -> "ModelConfig":
        """A reduced copy for CPU smoke tests."""
        return dataclasses.replace(self, **kw)


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init (standard for LM stacks)."""
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)
