"""Mixture-of-Experts.

Two execution paths sharing one weight layout:

* ``dense`` — every expert runs on every token, masked by top-k gates.
  O(E/k) FLOP overhead; used for tiny smoke configs and as the oracle in
  tests.
* ``ep`` — expert-parallel shard_map path.  Tokens stay batch-sharded and
  replicated over the ``model`` axis; each model-rank scatters its local
  experts' tokens into a capacity-bounded buffer (sort-based dispatch),
  runs the expert FFNs, scatters results back, and a psum over ``model``
  combines contributions.  Expert weights are EP-sharded over ``model``
  and FSDP-sharded over (pod, data) — the dp shards are all-gathered
  inside the shard_map (ZeRO-3 style).

Expert counts that do not divide the model axis are padded with
zero-initialized, never-routed experts (granite: 40 -> 48).

Routing scores are a softmax over the router's outputs (default) or,
DeepSeek-V3 style, per-expert sigmoids with a score-correction bias that
moves selection only (``noaux_tc``); the top-k gates are normalised and
scaled by ``routed_scale``.  The router runs in float32 at
``Precision.HIGHEST``, as the published gate does.

A layer may hold only a share of the experts (``first_held_expert``,
``held_experts``): one chip of an expert-parallel deployment.  It still
routes over every expert and returns its own experts' part of the
result, plus the shared experts; on one chip the dense path serves it,
every held expert on every token masked by the gates (no capacity, no
drops).  ``moe_apply_dense(..., valid=...)`` also counts the tokens
routed to each held expert.  The ``ep`` path serves neither a share nor
a selection bias and falls back to ``dense`` for them.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.layers.initializers import WSpec
from repro.layers.mlp import activation, mlp_apply, mlp_specs


def padded_experts(cfg) -> int:
    return cfg.expert_pad_to or cfg.n_experts


def held_experts(cfg) -> int:
    """Experts whose weights this layer holds."""
    return cfg.held_experts or padded_experts(cfg)


def moe_specs(cfg):
    E = held_experts(cfg)
    f = cfg.moe_d_ff or cfg.d_ff
    specs = {
        "router": WSpec((cfg.d_model, cfg.n_experts), (None, None), init="small"),
        "wi_gate": WSpec((E, cfg.d_model, f), ("experts", "embed", "expert_mlp"),
                         mxu_operand=True),
        "wi_up": WSpec((E, cfg.d_model, f), ("experts", "embed", "expert_mlp"),
                       mxu_operand=True),
        "wo": WSpec((E, f, cfg.d_model), ("experts", "expert_mlp", "embed"),
                    mxu_operand=True),
    }
    if cfg.router_bias:
        specs["router_bias"] = WSpec((cfg.n_experts,), (None,), init="zeros")
    if cfg.n_shared_experts:
        specs["shared"] = mlp_specs(cfg.d_model, f * cfg.n_shared_experts)
    return specs


def _route(tokens, router, cfg, bias=None):
    """tokens: (T, D) -> (gates (T,k), idx (T,k), aux_loss scalar).
    ``bias`` (n_experts,) is added to the scores for selection only."""
    logits = jnp.einsum("td,de->te", tokens.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    if bias is None:
        gates, idx = jax.lax.top_k(probs, cfg.experts_top_k)
    else:
        _, idx = jax.lax.top_k(probs + bias.astype(jnp.float32),
                               cfg.experts_top_k)
        gates = jnp.take_along_axis(probs, idx, axis=-1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scale != 1.0:
        gates = gates * cfg.routed_scale
    # load-balancing aux loss (Switch-style)
    frac = jnp.mean(
        jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32), axis=(0, 1)
    )
    imp = jnp.mean(probs, axis=0)
    aux = cfg.n_experts * jnp.sum(frac * imp)
    return gates, idx, aux


def moe_apply_dense(params, x, cfg, valid=None):
    """One-chip path: run every held expert on every token, combine with
    the top-k gates of the held experts (a token routed elsewhere gets
    nothing from them).  With ``valid`` (B, S) also returns the number of
    valid tokens routed to each held expert, (E_held,) int32."""
    B, S, D = x.shape
    E = held_experts(cfg)
    tokens = x.reshape(-1, D)
    gates, idx, aux = _route(tokens, params["router"], cfg,
                             params.get("router_bias"))
    # one_hot of an expert outside [first, first + E) is all zeros
    picks = jax.nn.one_hot(idx - cfg.first_held_expert, E, dtype=jnp.float32)
    comb = jnp.sum(picks * gates[..., None], axis=1)
    act = activation(cfg.act_fn)
    h_g = jnp.einsum("td,edf->etf", tokens, params["wi_gate"].astype(x.dtype))
    h_u = jnp.einsum("td,edf->etf", tokens, params["wi_up"].astype(x.dtype))
    h = act(h_g) * h_u
    y_e = jnp.einsum("etf,efd->etd", h, params["wo"].astype(x.dtype))
    y = jnp.einsum("etd,te->td", y_e.astype(jnp.float32), comb).astype(x.dtype)
    y = y.reshape(B, S, D)
    if cfg.n_shared_experts:
        y = y + mlp_apply(params["shared"], x, cfg.act_fn)
    if valid is None:
        return y, aux
    w = valid.reshape(-1, 1, 1).astype(jnp.float32)
    return y, aux, jnp.sum(picks * w, axis=(0, 1)).astype(jnp.int32)


def _dp_axes(mesh, batch: int) -> tuple[str, ...]:
    """Data axes usable for the token shard (must divide batch)."""
    axes = []
    prod = 1
    for a in ("pod", "data"):
        if a in mesh.shape and batch % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
    return tuple(axes)


def moe_apply_ep(params, x, cfg, mesh, *, capacity_factor: float = 1.25,
                 ep_axis: str = "model"):
    """Expert-parallel path (see module docstring)."""
    B, S, D = x.shape
    E = padded_experts(cfg)
    k = cfg.experts_top_k
    if (ep_axis not in mesh.shape or E % mesh.shape[ep_axis] != 0
            or cfg.held_experts or cfg.router_bias):
        return moe_apply_dense(params, x, cfg)
    ep_size = mesh.shape[ep_axis]
    E_loc = E // ep_size
    dp = _dp_axes(mesh, B)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    T_loc = (B // dp_size) * S
    C = max(1, int(math.ceil(T_loc * k * capacity_factor / cfg.n_experts)))

    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    x_spec = P(dp_spec, None, None)
    # expert weights: EP over model, FSDP over dp when divisible
    fsdp = dp_spec if (dp and D % dp_size == 0) else None
    w_spec = P(ep_axis, fsdp, None)
    wo_spec = P(ep_axis, None, fsdp)

    def f(x_loc, router, wig, wiu, wo):
        if fsdp is not None:
            wig = jax.lax.all_gather(wig, dp_spec, axis=1, tiled=True)
            wiu = jax.lax.all_gather(wiu, dp_spec, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, dp_spec, axis=2, tiled=True)
        tokens = x_loc.reshape(-1, D)
        T = tokens.shape[0]
        gates, idx, aux = _route(tokens, router, cfg)

        flat_e = idx.reshape(-1)                       # (T*k,)
        flat_g = gates.reshape(-1)
        order = jnp.argsort(flat_e)                    # stable
        se = flat_e[order]
        tok_ids = order // k
        sg = flat_g[order]
        starts = jnp.searchsorted(se, jnp.arange(E), side="left")
        pos = jnp.arange(T * k) - starts[se]
        e0 = jax.lax.axis_index(ep_axis) * E_loc
        local = (se >= e0) & (se < e0 + E_loc) & (pos < C)
        slot = jnp.where(local, (se - e0) * C + pos, E_loc * C)

        gathered = tokens[tok_ids] * local[:, None].astype(tokens.dtype)
        buf = jnp.zeros((E_loc * C + 1, D), x_loc.dtype).at[slot].set(gathered)
        bufe = buf[:-1].reshape(E_loc, C, D)

        act = activation(cfg.act_fn)
        h = act(jnp.einsum("ecd,edf->ecf", bufe, wig.astype(x_loc.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", bufe, wiu.astype(x_loc.dtype))
        out = jnp.einsum("ecf,efd->ecd", h, wo.astype(x_loc.dtype))
        out_flat = out.reshape(E_loc * C, D)

        contrib = out_flat[jnp.where(local, slot, 0)]
        contrib = contrib * (sg * local).astype(contrib.dtype)[:, None]
        y = jnp.zeros((T, D), x_loc.dtype).at[tok_ids].add(contrib)
        y = jax.lax.psum(y, ep_axis)
        # aux identical on every ep rank (same tokens) — mean over dp shards
        if dp:
            aux = jax.lax.pmean(aux, dp_spec)
        return y.reshape(x_loc.shape), aux

    y, aux = jax.shard_map(
        f, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, wo_spec),
        out_specs=(x_spec, P()), check_vma=False,
    )(x, params["router"], params["wi_gate"], params["wi_up"], params["wo"])

    if cfg.n_shared_experts:
        y = y + mlp_apply(params["shared"], x, cfg.act_fn)
    return y, aux


def moe_apply(params, x, cfg, mesh=None, impl: str = "dense"):
    if impl == "ep" and mesh is not None:
        return moe_apply_ep(params, x, cfg, mesh)
    return moe_apply_dense(params, x, cfg)
