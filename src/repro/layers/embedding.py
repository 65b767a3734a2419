"""Token embedding and LM output head."""

from __future__ import annotations

import jax.numpy as jnp

from repro.layers.initializers import WSpec


def embed_specs(vocab: int, d_model: int):
    return {"table": WSpec((vocab, d_model), ("vocab", "embed"), init="embed", scale=0.02)}


def embed_apply(params, ids, *, scale: float = 1.0, dtype=jnp.bfloat16):
    out = params["table"][ids].astype(dtype)
    if scale != 1.0:
        out = out * jnp.asarray(scale, dtype)
    return out


def head_specs(d_model: int, vocab: int):
    return {"w": WSpec((d_model, vocab), ("embed", "vocab"), init="small",
                       mxu_operand=True)}


def head_apply(params, x, *, softcap: float = 0.0, tied_table=None):
    if tied_table is not None:
        logits = jnp.einsum("bsd,vd->bsv", x, tied_table.astype(x.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["w"].astype(x.dtype))
    logits = logits.astype(jnp.float32)
    if softcap and softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits
