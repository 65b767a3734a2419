"""Operations and bytes that the CLIP towers' launches need, from the
part's sizes (``parts.clip.sizes``): every weight read once per launch at
4 bytes a float32 parameter, inputs read and embeddings written once,
multiply-adds counted as two operations.  The text tower's attention is
causal, so it counts half the score matrix."""

from __future__ import annotations


def _tower_params(W: int, L: int) -> int:
    # q, k, v, o (4 W^2) and the gated MLP of 4W (3 * 4 W^2), 2 LayerNorms
    return L * (16 * W * W + 4 * W) + 2 * W


def vit(s: dict, images: int) -> tuple[float, float]:
    """One vision launch over ``images`` images: (operations, bytes)."""
    N, W, L, E = s["n_image_tokens"], s["v_width"], s["v_layers"], s["embed_dim"]
    flops = images * (2 * N * W * W                 # patch projection
                      + L * (32 * N * W * W         # 16 W^2 weights / token
                             + 4 * N * N * W)       # q.k and p.v
                      + 2 * W * E)
    params = _tower_params(W, L) + W * W + N * W + W * E
    nbytes = 4 * (params + images * (N * W + E))
    return float(flops), float(nbytes)


def text(s: dict, sequences: int) -> tuple[float, float]:
    """One text launch over ``sequences`` captions: (operations, bytes)."""
    S, W, L, E = s["context"], s["t_width"], s["t_layers"], s["embed_dim"]
    flops = sequences * (L * (32 * S * W * W + 2 * S * (S + 1) * W)
                         + 2 * W * E)
    params = _tower_params(W, L) + S * W + W * E
    nbytes = 4 * (params + sequences * (S * W + E)) + 4 * sequences * S
    return float(flops), float(nbytes)
