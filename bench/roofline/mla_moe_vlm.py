"""Operations and bytes that the MLA + MoE decoder's work needs, from its
sizes (``parts.mla_moe_vlm.sizes``), the lengths actually served and
the routing the program reports.

Counts are of the algorithm, not of what the program happens to compute:
only the routed (token, held expert) pairs do expert work, and only the
held experts some token reached are read (``local_pairs`` and
``touched``, from the program's ``decode_tick`` and ``prefill`` tags);
decode attention is absorbed (scores against the latent cache at the
real lengths), prefill attention expanded and causal; every other weight
is read once per call, at 2 bytes a parameter (the part stores weights
in bfloat16), and the latent cache at 4 bytes an element (float32).
Multiply-adds count as two operations.
"""

from __future__ import annotations

WEIGHT_BYTES = 2


def attn_params(s: dict) -> int:
    """Matrix parameters of one layer's latent attention."""
    d, H, R = s["hidden_size"], s["num_attention_heads"], s["kv_lora_rank"]
    nope, rope, vd = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                      s["v_head_dim"])
    return (d * H * (nope + rope) + d * R + d * rope + R * H * nope
            + R * H * vd + H * vd * d)


def expert_params(s: dict) -> int:
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def _layers(s: dict) -> tuple[int, int, int]:
    K = s["first_k_dense_replace"]
    return s["num_hidden_layers"], K, s["num_hidden_layers"] - K


def dense_token_params(s: dict) -> int:
    """Matrix parameters every token multiplies, experts aside: attention,
    the dense layers' SwiGLU, the routers and the shared experts."""
    d = s["hidden_size"]
    L, K, M = _layers(s)
    shared = 3 * d * s["moe_intermediate_size"] * s["n_shared_experts"]
    return (L * attn_params(s) + K * 3 * d * s["intermediate_size"]
            + M * (d * s["router_experts"] + shared))


def resident_bytes(s: dict, bytes_per_param: int = WEIGHT_BYTES) -> int:
    """Weights a call reads whatever it routes: every matrix but the
    experts and the embedding table, plus the norm scales."""
    d, V, R = s["hidden_size"], s["vocab_size"], s["kv_lora_rank"]
    L, _, M = _layers(s)
    norms = L * (2 * d + R) + d
    return (dense_token_params(s) + M * s["router_experts"] + d * V
            + norms) * bytes_per_param


def kv_bytes_per_token(s: dict, bytes_per_elem: int = 4) -> int:
    return (s["num_hidden_layers"]
            * (s["kv_lora_rank"] + s["qk_rope_head_dim"]) * bytes_per_elem)


def decode_step(s: dict, lengths: list[int], local_pairs: int,
                touched: int) -> tuple[float, float]:
    """One batched decode step over live rows whose caches hold
    ``lengths`` tokens before the step, with ``local_pairs`` (token,
    held expert) picks over ``touched`` (layer, held expert) pairs:
    (operations, bytes)."""
    d, V, H = s["hidden_size"], s["vocab_size"], s["num_attention_heads"]
    R, rope = s["kv_lora_rank"], s["qk_rope_head_dim"]
    L = s["num_hidden_layers"]
    rows = len(lengths)
    flops = (2 * rows * (dense_token_params(s) + d * V)
             + 2 * local_pairs * expert_params(s)
             + sum(2 * L * H * (2 * R + rope) * (n + 1) for n in lengths))
    kv = kv_bytes_per_token(s)
    nbytes = (resident_bytes(s) + touched * expert_params(s) * WEIGHT_BYTES
              + rows * d * WEIGHT_BYTES               # embedding rows
              + sum(n * kv for n in lengths)          # latent cache read
              + rows * kv)                            # new position written
    return float(flops), float(nbytes)


def prefill(s: dict, prompt_tokens: int, local_pairs: int,
            touched: int) -> tuple[float, float]:
    """Batch-1 prefill of the image prefix and a prompt with its routing:
    (operations, bytes).  The vision stub's projection is the
    encoder's, not here."""
    d, V, H = s["hidden_size"], s["vocab_size"], s["num_attention_heads"]
    nope, rope, vd = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                      s["v_head_dim"])
    L = s["num_hidden_layers"]
    n_img = s["n_image_tokens"]
    T = n_img + prompt_tokens
    flops = (2 * T * dense_token_params(s)
             + 2 * local_pairs * expert_params(s)
             + L * H * (nope + rope + vd) * T * (T + 1)   # causal q.k, p.v
             + 2 * n_img * d * d                           # image projection
             + 2 * d * V)                                  # last logits
    nbytes = (resident_bytes(s) + touched * expert_params(s) * WEIGHT_BYTES
              + d * d * WEIGHT_BYTES                       # image projection
              + prompt_tokens * d * WEIGHT_BYTES           # embedding rows
              + T * kv_bytes_per_token(s))                 # cache written
    return float(flops), float(nbytes)
