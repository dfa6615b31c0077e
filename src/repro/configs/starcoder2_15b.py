"""starcoder2-15b [dense]: the published block (BigCode, "StarCoder 2 and
The Stack v2", arXiv:2402.19173; bigcode/starcoder2-15b ``config.json``).

40 layers, d_model 6144, 48 query heads and 4 KV heads of 128 (GQA 12:1),
d_ff 24576, vocab 49152, 16384 positions.  Pre-norm blocks with LayerNorm
(weight and bias, eps 1e-5) and a final LayerNorm; biases on q, k, v, o,
c_fc and c_proj (``use_bias``); a non-gated MLP c_fc -> tanh GELU
(``gelu_pytorch_tanh``) -> c_proj; rotary positions (theta 1e5, halves
rotated); a sliding window of 4096 on every layer; embeddings not scaled.
The output head is kept untied from the embedding (assumed)."""

from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="starcoder2-15b",
    vocab=49152,
    d_model=6144,
    n_layers=40,
    n_heads=48,
    n_kv_heads=4,
    head_dim=128,
    d_ff=24576,
    attn_type="gqa",
    qkv_bias=True,
    proj_bias=True,
    local_window=4096,
    layer_pattern="local",
    norm_type="layer",
    act="gelu",
    gated_mlp=False,
    rope_theta=100_000.0,
    embed_scale=False,
)

SMOKE = CONFIG.scaled(
    vocab=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, local_window=8,
)

FAMILY = "dense"
SKIP_LONG = ("window layers keep a full-length cache (524288 positions x 40 "
             "layers)")
