"""Operations and bytes that the VLM decoder's work needs, from its sizes
(``parts.vlm.sizes``) and the lengths actually served.

Counts are of the algorithm, not of what the program happens to compute:
causal attention over the real lengths, the KV cache read once per
position that exists, every weight read once per call at the served
width (4 bytes per float32 parameter).  Multiply-adds count as two
operations.
"""

from __future__ import annotations


def _layer_matmul_params(s: dict) -> int:
    d, H, K, hd, F = (s["hidden_size"], s["num_attention_heads"],
                      s["num_key_value_heads"], s["head_dim"],
                      s["intermediate_size"])
    return d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * F


def head_weight_bytes(s: dict, bytes_per_param: int = 4) -> int:
    """Decoder weights (the tied table once, image projection included)."""
    d, L, V = s["hidden_size"], s["num_hidden_layers"], s["vocab_size"]
    n = L * (_layer_matmul_params(s) + 2 * d) + V * d + d + d * d
    return n * bytes_per_param


def kv_bytes_per_token(s: dict, bytes_per_elem: int = 4) -> int:
    return (2 * s["num_hidden_layers"] * s["num_key_value_heads"]
            * s["head_dim"] * bytes_per_elem)


def decode_step(s: dict, lengths: list[int]) -> tuple[float, float]:
    """One batched decode step over live rows whose caches hold
    ``lengths`` tokens before the step: (operations, bytes)."""
    d, L, V = s["hidden_size"], s["num_hidden_layers"], s["vocab_size"]
    H, hd = s["num_attention_heads"], s["head_dim"]
    rows = len(lengths)
    per_row = 2 * L * _layer_matmul_params(s) + 2 * d * V
    attn = sum(4 * L * H * hd * (n + 1) for n in lengths)
    flops = rows * per_row + attn
    kv = kv_bytes_per_token(s)
    nbytes = (head_weight_bytes(s) - d * d * 4        # no image projection
              + sum(n * kv for n in lengths)          # cache read
              + rows * kv)                            # new position written
    return float(flops), float(nbytes)


def prefill(s: dict, prompt_tokens: int) -> tuple[float, float]:
    """Batch-1 prefill of the image prefix and a prompt: (operations,
    bytes).  The vision stub's projection is the encoder's, not here."""
    d, L, V = s["hidden_size"], s["num_hidden_layers"], s["vocab_size"]
    H, hd = s["num_attention_heads"], s["head_dim"]
    T = s["n_image_tokens"] + prompt_tokens
    flops = (2 * T * L * _layer_matmul_params(s)
             + 2 * L * H * hd * T * (T + 1)          # causal q.k and p.v
             + 2 * s["n_image_tokens"] * d * d       # image projection
             + 2 * d * V)                            # last position's logits
    nbytes = head_weight_bytes(s) + T * kv_bytes_per_token(s)
    return float(flops), float(nbytes)

