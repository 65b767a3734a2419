"""A vision-language deployment part: a vision stub module shared by
generative tasks that also share one decoder head (``vlm-head``).

The part's entry in a configuration file gives the decoder's published
sizes under ``llm_config`` (Hugging Face key names), the image prefix
under ``n_image_tokens``, and the tasks.  This file builds the program's
modules from those sizes, draws every weight on the device in one jitted
call from the seed, makes request payloads, and checks served tokens
against ``bench/reference/qwen2_vlm.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import qwen2_vlm

KIND = "generative"


def sizes(part: dict) -> dict:
    c = part["llm_config"]
    return {
        "hidden_size": c["hidden_size"],
        "num_hidden_layers": c["num_hidden_layers"],
        "num_attention_heads": c["num_attention_heads"],
        "num_key_value_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim",
                          c["hidden_size"] // c["num_attention_heads"]),
        "intermediate_size": c["intermediate_size"],
        "vocab_size": c["vocab_size"],
        "rope_theta": float(c["rope_theta"]),
        "rms_norm_eps": float(c["rms_norm_eps"]),
        "n_image_tokens": part["n_image_tokens"],
    }


def weight_shapes(s: dict) -> dict:
    d, L = s["hidden_size"], s["num_hidden_layers"]
    H, K, hd = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    F, V = s["intermediate_size"], s["vocab_size"]
    return {
        "enc_w": (d, d), "img_proj": (d, d), "embed": (V, d),
        "ln1": (L, d), "wq": (L, d, H, hd), "wk": (L, d, K, hd),
        "wv": (L, d, K, hd), "wo": (L, H, hd, d), "ln2": (L, d),
        "w_gate": (L, d, F), "w_up": (L, d, F), "w_down": (L, F, d),
        "final_norm": (d,),
    }


def n_params(s: dict, *, head_only: bool = False) -> int:
    shapes = weight_shapes(s)
    skip = ("enc_w",) if head_only else ()
    return int(sum(math.prod(v) for k, v in shapes.items() if k not in skip))


def make_weights(s: dict, key, device):
    """Every weight of the part, drawn on ``device`` in one jitted call:
    fan-in scaled normals for matrices, 0.02 for the embedding (the
    published initializer range), ones for the norm scales."""
    shapes = weight_shapes(s)
    d, hd = s["hidden_size"], s["head_dim"]
    fan_in = {"enc_w": d, "img_proj": d, "wq": d, "wk": d, "wv": d,
              "wo": s["num_attention_heads"] * hd, "w_gate": d, "w_up": d,
              "w_down": s["intermediate_size"]}

    def init(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if name in ("ln1", "ln2", "final_norm"):
                out[name] = jnp.ones(shape, jnp.float32)
                continue
            std = 0.02 if name == "embed" else 1.0 / math.sqrt(fan_in[name])
            out[name] = std * jax.random.normal(jax.random.fold_in(key, i),
                                                shape, jnp.float32)
        return out

    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(init, out_shardings=sharding)(key)


def program_params(w: dict) -> dict:
    """The same arrays arranged as the program's parameter tree."""
    return {
        "embed": {"table": w["embed"]},
        "stages": {"blocks": {"blocks": {
            "ln_attn": {"scale": w["ln1"]},
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"]},
            "ln_mlp": {"scale": w["ln2"]},
            "mlp": {"wi_gate": w["w_gate"], "wi_up": w["w_up"],
                    "wo": w["w_down"]},
        }}},
        "final_norm": {"scale": w["final_norm"]},
        "img_proj": {"w": w["img_proj"]},
    }


def arch_config(s: dict):
    from repro.common.config import ArchConfig

    return ArchConfig(
        name="vlm-head", family="vlm", n_layers=s["num_hidden_layers"],
        d_model=s["hidden_size"], n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_key_value_heads"], d_ff=s["intermediate_size"],
        vocab_size=s["vocab_size"], head_dim=s["head_dim"],
        rope_theta=s["rope_theta"], norm_eps=s["rms_norm_eps"],
        tie_embeddings=True, has_vision_stub=True,
        n_image_tokens=s["n_image_tokens"])


def pix_encode(w, x):
    """The vision stub: precomputed patch embeddings through one
    projection (named, so its compiled program is ``jit_pix_encode``)."""
    return jnp.tanh(x @ w)


def build(part: dict, weights: dict, serve: dict):
    """(model specs, module builders, module roles) for the program."""
    from repro.core.module import ModelSpec, ModuleSpec
    from repro.models.api import build_model

    s = sizes(part)
    cfg = arch_config(s)
    bundle = build_model(cfg, compute_dtype=jnp.float32)
    params = program_params(weights)
    want = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                               jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter tree for this configuration")
    d, n_img = s["hidden_size"], s["n_image_tokens"]
    enc_name, head_name = part["encoder"], part["head"]
    n_head = n_params(s, head_only=True)
    enc = ModuleSpec(enc_name, "encoder", "vision", d * d,
                     bytes_per_param=4.0, flops_per_query=2.0 * n_img * d * d,
                     input_bytes=4 * n_img * d, output_bytes=4 * n_img * d)
    head = ModuleSpec(
        head_name, "head", "task", n_head, bytes_per_param=4.0,
        generative=True, flops_per_query=2.0 * n_head * serve["max_seq_len"],
        input_bytes=4 * n_img * d,
        kv_bytes_per_token=2 * s["num_hidden_layers"]
        * s["num_key_value_heads"] * s["head_dim"] * 4)
    builders = {enc_name: lambda: (pix_encode, weights["enc_w"]),
                head_name: lambda: (bundle, params)}
    models = [ModelSpec(t, t, (enc,), head) for t in part["tasks"]]
    roles = {"decoder": head_name, "encoders": [enc_name]}
    return models, builders, roles


def make_pools(part: dict, key, device, pool_size: int) -> dict:
    """Request payloads on the device: ``pool_size`` images of
    precomputed patch embeddings (n_image_tokens x hidden)."""
    s = sizes(part)
    shape = (pool_size, s["n_image_tokens"], s["hidden_size"])
    sharding = jax.sharding.SingleDeviceSharding(device)
    imgs = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                   out_shardings=sharding)(key)
    return {"image": [imgs[i] for i in range(pool_size)]}


def make_request(part: dict, arrival, rid: int, source: str, pools: dict,
                 rng: np.random.Generator):
    from repro.s2m3 import Request

    s = sizes(part)
    prompt = tuple(int(t) for t in rng.integers(1, s["vocab_size"],
                                                arrival.prompt_tokens))
    return Request(rid, arrival.task, source, prompt=prompt,
                   max_new_tokens=arrival.new_tokens, temperature=0.0,
                   inputs={"vision": pools["image"][arrival.payload]})


def check(part: dict, weights: dict, served: list, *, serve: dict,
          control: bool = False) -> dict:
    """Teacher-forced reference over each ``(request, tokens)`` pair.

    Returns the widest gap by which a served token's reference logit lies
    below the reference's best (``served_gap``), the mean of those gaps
    over the served tokens (``served_gap_mean``), and the number of
    tokens compared; with ``control`` the same two numbers for the tokens
    that an fp8 copy of the reference puts first at each position.
    """
    s = sizes(part)
    n_img = s["n_image_tokens"]
    T = serve["max_seq_len"] - n_img
    items = tuple(sorted(s.items()))
    gaps, cgaps = [], []
    for req, toks in served:
        P, n = len(req.prompt), len(toks)
        seq = np.zeros((T,), np.int32)
        seq[:P] = req.prompt
        seq[P:P + n] = toks
        pos = np.zeros((T,), np.int32)
        pos[:n] = n_img + P - 1 + np.arange(n)
        tok = np.zeros((T,), np.int32)
        tok[:n] = toks
        valid = np.arange(T) < n
        gap, cgap = qwen2_vlm.score(
            weights, req.inputs["vision"], jnp.asarray(seq), jnp.asarray(pos),
            jnp.asarray(tok), jnp.asarray(valid), sizes_items=items,
            control=control)
        gaps.append(np.asarray(gap)[:n])
        cgaps.append(np.asarray(cgap)[:n])
    gaps, cgaps = np.concatenate(gaps), np.concatenate(cgaps)
    out = {"served_gap": float(gaps.max()),
           "served_gap_mean": float(gaps.mean()),
           "tokens_compared": int(gaps.size)}
    if control:
        out["control_gap"] = float(cgaps.max())
        out["control_gap_mean"] = float(cgaps.mean())
    return out
