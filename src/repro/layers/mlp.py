"""Gated MLP (SwiGLU / GeGLU)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.layers.initializers import WSpec


def mlp_specs(d_model: int, d_ff: int):
    return {
        "wi_gate": WSpec((d_model, d_ff), ("embed", "mlp"), mxu_operand=True),
        "wi_up": WSpec((d_model, d_ff), ("embed", "mlp"), mxu_operand=True),
        "wo": WSpec((d_ff, d_model), ("mlp", "embed"), mxu_operand=True),
    }


def activation(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
        "relu": jax.nn.relu,
    }[name]


def mlp_apply(params, x, act_fn: str = "silu"):
    act = activation(act_fn)
    g = jnp.einsum("bsd,df->bsf", x, params["wi_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, params["wi_up"].astype(x.dtype))
    h = act(g) * u
    return jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(x.dtype))
