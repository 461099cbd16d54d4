"""Operations and bytes that the work needs, computed from shapes.

These are the yardstick's own counts: the numerators of MFU and of the
kernels' roofline shares. They count what the algorithm requires, not
what an implementation happens to execute, so recomputation (rematerial-
ised forward passes) and padding added by a kernel's tiling do not count.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# Models
# ---------------------------------------------------------------------------

def decoder_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    every layer's attention and MLP projections and the LM head. The
    embedding lookup multiplies nothing."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = d // heads
    ff = cfg["intermediate_size"]
    per_layer = d * heads * hd + 2 * d * kv * hd + heads * hd * d + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def decoder_forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward FLOPs per token: 2 per matmul parameter, plus causal
    attention (scores and weighted values) over a mean context of
    (seq + 1) / 2 keys."""
    d = cfg["hidden_size"]
    attn = 2 * 2 * d * (seq + 1) / 2 * cfg["num_hidden_layers"]
    return 2.0 * decoder_matmul_params(cfg) + attn


def decoder_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward)."""
    return 3.0 * decoder_forward_flops_per_token(cfg, seq)


def cnn_forward_flops_per_sample(cfg: dict) -> float:
    """The paper's CNN: two 3x3 SAME convolutions, each followed by a 2x2
    max-pool, a hidden dense layer and the classifier."""
    img, cin, w = cfg["image"], cfg["in_channels"], cfg["width"]
    conv1 = 2.0 * img * img * w * 9 * cin
    half = img // 2
    conv2 = 2.0 * half * half * (2 * w) * 9 * w
    flat = (img // 4) ** 2 * 2 * w
    dense = 2.0 * (flat * cfg["hidden"] + cfg["hidden"] * cfg["classes"])
    return conv1 + conv2 + dense


def cnn_param_count(cfg: dict) -> int:
    cin, w, h, c = cfg["in_channels"], cfg["width"], cfg["hidden"], cfg["classes"]
    flat = (cfg["image"] // 4) ** 2 * 2 * w
    return 9 * cin * w + 9 * w * 2 * w + flat * h + h + h * c + c


# ---------------------------------------------------------------------------
# Kernels: bytes each call must move between HBM and the core
# ---------------------------------------------------------------------------

def stoch_quant_pack_bytes(n: int) -> int:
    """One client's compress of n coordinates: read the f32 delta, write
    one bit per coordinate. The scale b, the uniforms and the packing
    matrix are operands of this implementation, not of the algorithm."""
    return 4 * n + -(-n // 8)


def bit_count_bytes(m: int, n: int) -> int:
    """Count of m clients' packed rows of n coordinates: read m rows of
    n/8 bytes, write one int32 count per coordinate."""
    return m * -(-n // 8) + 4 * n


def prox_sgd_bytes(n: int) -> int:
    """One fused prox-SGD step: read w, w0, grad, momentum, write w and
    momentum, all f32."""
    return 6 * 4 * n
