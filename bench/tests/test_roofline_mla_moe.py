"""Operations and bytes of the MLA + MoE decoder, against hand counts at
small sizes, and the full-width step's bound."""

import json
from pathlib import Path

import pytest

import harness
from roofline import mla_moe_vlm as rm

# d 8, 2 heads of nope 2 + rope 2, v 2, latent 4; 3 layers (1 dense), 4
# router outputs, experts of width 3, one shared expert, dense width 5
S = {"hidden_size": 8, "num_attention_heads": 2, "kv_lora_rank": 4,
     "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2,
     "num_hidden_layers": 3, "first_k_dense_replace": 1,
     "router_experts": 4, "moe_intermediate_size": 3, "n_shared_experts": 1,
     "intermediate_size": 5, "vocab_size": 10, "n_image_tokens": 3}
# q 8*2*4 + dkv 8*4 + kr 8*2 + uk 4*2*2 + uv 4*2*2 + o 2*2*8
ATTN = 64 + 32 + 16 + 16 + 16 + 32
DENSE = 3 * ATTN + 3 * 8 * 5 + 2 * (8 * 4 + 3 * 8 * 3)   # per token
EXPERT = 3 * 8 * 3
KV = 3 * (4 + 2) * 4                                      # bytes a token
RESIDENT = (DENSE + 2 * 4 + 8 * 10 + 3 * (16 + 4) + 8) * 2   # bf16


def test_decode_step_hand_count():
    flops, nbytes = rm.decode_step(S, [5, 9], local_pairs=7, touched=3)
    attn = 2 * 3 * 2 * (2 * 4 + 2) * (6 + 10)
    assert flops == 2 * 2 * (DENSE + 8 * 10) + 2 * 7 * EXPERT + attn
    assert nbytes == (RESIDENT + 3 * EXPERT * 2 + 2 * 8 * 2
                      + (5 + 9) * KV + 2 * KV)


def test_prefill_hand_count():
    flops, nbytes = rm.prefill(S, 4, local_pairs=11, touched=4)
    T = 3 + 4
    assert flops == (2 * T * DENSE + 2 * 11 * EXPERT
                     + 3 * 2 * (2 + 2 + 2) * T * (T + 1)
                     + 2 * 3 * 8 * 8 + 2 * 8 * 10)
    assert nbytes == (RESIDENT + 4 * EXPERT * 2 + 8 * 8 * 2 + 4 * 8 * 2
                      + T * KV)


def test_full_width_counts_match_the_part_and_bind_on_bytes():
    cfg = json.loads((Path(harness.BENCH) / "configs" /
                      "kimi-vl-a3b-moe-mt.json").read_text())
    fam = harness.family("mla_moe_vlm")
    s = fam.sizes(cfg["parts"][0])
    assert rm.kv_bytes_per_token(s) == fam.kv_bytes_per_token(s)
    L, K = s["num_hidden_layers"], s["first_k_dense_replace"]
    held = (L - K) * s["n_routed_experts"]
    # every weight but the embedding table and the vision stub, once
    table = s["vocab_size"] * s["hidden_size"]
    stub = 2 * s["hidden_size"] ** 2
    assert rm.resident_bytes(s) + held * rm.expert_params(s) * 2 == (
        2 * (fam.n_params(s) - table - stub))
    # 64 rows at some 1.1k positions, every held expert touched
    flops, nbytes = rm.decode_step(s, [1100] * 64, 64 * 6 * 8 // 4, held)
    assert nbytes / 819e9 > flops / 197e12
    # bf16 weights 3.55 GB, the latent cache 1.46 GB: 6.1 ms at 819 GB/s
    assert nbytes == pytest.approx(5.01e9, rel=0.01)
