"""Operations and bytes of the served programs, against hand counts at
small sizes."""

import pytest

from roofline import clip as rc
from roofline import vlm as rv

S = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 4,
     "num_key_value_heads": 2, "head_dim": 2, "intermediate_size": 16,
     "vocab_size": 10, "n_image_tokens": 3}
# per layer: q 8*4*2 + k,v 2*8*2*2 + o 4*2*8 + mlp 3*8*16 = 64+64+64+384
LAYER = 576
KV_TOKEN = 2 * 2 * 2 * 2 * 4              # k,v x layers x kv heads x hd x 4 B


def test_decode_step_hand_count():
    flops, nbytes = rv.decode_step(S, [5, 9])
    per_row = 2 * 2 * LAYER + 2 * 8 * 10
    attn = 4 * 2 * 4 * 2 * (6 + 10)
    assert flops == 2 * per_row + attn
    weights = (2 * (LAYER + 16) + 10 * 8 + 8) * 4   # no image projection
    assert nbytes == weights + (5 + 9) * KV_TOKEN + 2 * KV_TOKEN


def test_prefill_hand_count():
    flops, nbytes = rv.prefill(S, 4)
    T = 3 + 4
    assert flops == (2 * T * 2 * LAYER + 2 * 2 * 4 * 2 * T * (T + 1)
                     + 2 * 3 * 8 * 8 + 2 * 8 * 10)
    assert nbytes == (2 * (LAYER + 16) + 10 * 8 + 8 + 64) * 4 + T * KV_TOKEN


C = {"n_image_tokens": 4, "v_width": 8, "v_layers": 1, "embed_dim": 2,
     "context": 3, "t_width": 4, "t_layers": 1}


def test_vit_hand_count():
    flops, nbytes = rc.vit(C, 2)
    # patch proj 2*4*64, layer 32*4*64 + 4*16*8, proj 2*8*2
    assert flops == 2 * (512 + 8192 + 512 + 32)
    params = (16 * 64 + 4 * 8) + 2 * 8 + 64 + 4 * 8 + 8 * 2
    assert nbytes == 4 * (params + 2 * (4 * 8 + 2))


def test_text_hand_count():
    flops, nbytes = rc.text(C, 5)
    # layer 32*3*16 + causal 2*3*4*4, proj 2*4*2
    assert flops == 5 * (1536 + 96 + 16)
    params = (16 * 16 + 4 * 4) + 2 * 4 + 3 * 4 + 4 * 2
    assert nbytes == 4 * (params + 5 * (3 * 4 + 2)) + 4 * 5 * 3


def test_full_width_decode_is_bound_by_bytes():
    import json
    from pathlib import Path

    import harness

    cfg = json.loads((Path(harness.BENCH) / "configs" /
                      "qwen2-0.5b-vlm-mt.json").read_text())
    fam = harness.family("vlm")
    s = fam.sizes(cfg["parts"][0])
    assert rv.head_weight_bytes(s) == pytest.approx(
        4 * fam.n_params(s, head_only=True))
    flops, nbytes = rv.decode_step(s, [300] * 32)
    assert nbytes / 819e9 > flops / 197e12
