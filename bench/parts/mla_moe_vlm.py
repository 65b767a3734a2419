"""A vision-language deployment part whose decoder is the DeepSeek-V3
block (latent attention, sigmoid-routed experts with shared experts and
leading dense layers), served on one chip of an expert-parallel
deployment: a vision stub module shared by generative tasks that also
share one decoder head (``vlm-head``).

The part's entry in a configuration file gives the decoder's sizes
under ``llm_config`` (Hugging Face key names, as in Kimi-VL-A3B's
``text_config``), where ``n_routed_experts`` counts the experts this
chip holds and ``num_hidden_layers`` the layers it serves;
``router_experts`` is the router's width (every expert of the layer)
and ``first_held_expert`` the id of the first held one.  This file
builds the program's modules from those sizes, draws every weight on the
device in one jitted call from the seed, makes request payloads, and
checks served tokens against ``bench/reference/kimi_vl_dec.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import kimi_vl_dec

KIND = "generative"
BIAS_STD = 0.05          # e_score_correction_bias, drawn from the seed
# a gap above LARGE_GAP counts as large: the share of such tokens is
# bounded beside the mean, and their routing margins are logged
LARGE_GAP = 0.3
# float32 storage does not fit one chip: XLA hoists a bfloat16 copy of
# every expert weight out of the decode step's layer scan (4.1 GB of
# temporaries beside 10.2 GB of weights and pool)
WEIGHT_DTYPE = jnp.bfloat16


def sizes(part: dict) -> dict:
    c = part["llm_config"]
    if c.get("q_lora_rank"):
        raise ValueError("this part serves MLA with a direct q projection "
                         "(q_lora_rank null)")
    if c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc":
        raise ValueError("this part routes by sigmoid scores with a "
                         "selection-only bias (noaux_tc)")
    return {
        "hidden_size": c["hidden_size"],
        "num_hidden_layers": c["num_hidden_layers"],
        "num_attention_heads": c["num_attention_heads"],
        "intermediate_size": c["intermediate_size"],
        "moe_intermediate_size": c["moe_intermediate_size"],
        "n_routed_experts": c["n_routed_experts"],
        "router_experts": part["router_experts"],
        "first_held_expert": part["first_held_expert"],
        "num_experts_per_tok": c["num_experts_per_tok"],
        "n_shared_experts": c["n_shared_experts"],
        "first_k_dense_replace": c["first_k_dense_replace"],
        "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_head_dim": c["qk_nope_head_dim"],
        "qk_rope_head_dim": c["qk_rope_head_dim"],
        "v_head_dim": c["v_head_dim"],
        "routed_scaling_factor": float(c["routed_scaling_factor"]),
        "rope_theta": float(c["rope_theta"]),
        "rms_norm_eps": float(c["rms_norm_eps"]),
        "vocab_size": c["vocab_size"],
        "n_image_tokens": part["n_image_tokens"],
    }


def _attn_shapes(s: dict, n: int) -> dict:
    d, H = s["hidden_size"], s["num_attention_heads"]
    nope, rope = s["qk_nope_head_dim"], s["qk_rope_head_dim"]
    R, vd = s["kv_lora_rank"], s["v_head_dim"]
    return {"ln1": (n, d), "ln2": (n, d), "wq": (n, d, H, nope + rope),
            "w_dkv": (n, d, R), "kv_norm": (n, R), "w_kr": (n, d, rope),
            "w_uk": (n, R, H, nope), "w_uv": (n, R, H, vd),
            "wo": (n, H, vd, d)}


def weight_shapes(s: dict) -> dict:
    d, V = s["hidden_size"], s["vocab_size"]
    K = s["first_k_dense_replace"]
    M = s["num_hidden_layers"] - K
    E, N, f = (s["n_routed_experts"], s["router_experts"],
               s["moe_intermediate_size"])
    Fd, Fs = s["intermediate_size"], f * s["n_shared_experts"]
    return {
        "enc_w": (d, d), "img_proj": (d, d), "embed": (V, d),
        "head": (d, V), "final_norm": (d,),
        "dense": {**_attn_shapes(s, K), "w_gate": (K, d, Fd),
                  "w_up": (K, d, Fd), "w_down": (K, Fd, d)},
        "moe": {**_attn_shapes(s, M), "router": (M, d, N),
                "router_bias": (M, N), "w_gate": (M, E, d, f),
                "w_up": (M, E, d, f), "w_down": (M, E, f, d),
                "shared_gate": (M, d, Fs), "shared_up": (M, d, Fs),
                "shared_down": (M, Fs, d)},
    }


def _leaves(shapes: dict, prefix: str = ""):
    for k, v in sorted(shapes.items()):
        if isinstance(v, dict):
            yield from _leaves(v, f"{prefix}{k}/")
        else:
            yield f"{prefix}{k}", v


def n_params(s: dict, *, head_only: bool = False) -> int:
    return int(sum(math.prod(v) for k, v in _leaves(weight_shapes(s))
                   if not (head_only and k == "enc_w")))


def _fan_in(name: str, shape: tuple, s: dict) -> int:
    """The input width each matrix multiplies: its second-to-last axes
    that are not stacked layers or experts."""
    base = name.rsplit("/", 1)[-1]
    if base == "wo":
        return s["num_attention_heads"] * s["v_head_dim"]
    if base in ("w_uk", "w_uv"):
        return s["kv_lora_rank"]
    if base in ("w_down", "shared_down"):
        return shape[-2]
    return s["hidden_size"]


def make_weights(s: dict, key, device):
    """Every weight of the part, drawn on ``device`` in one jitted call
    and stored in bfloat16, the checkpoint's published dtype: fan-in
    scaled normals for matrices, 0.02 for the embedding, ones for the
    norm scales, normals of ``BIAS_STD`` for the router's score
    correction.  The program computes in float32 from them (its matmuls
    at default precision round their operands to bfloat16 anyway); the
    reference reads the same values at full precision."""
    leaves = list(_leaves(weight_shapes(s)))

    def init(key):
        flat = {}
        for i, (name, shape) in enumerate(leaves):
            base = name.rsplit("/", 1)[-1]
            k = jax.random.fold_in(key, i)
            if base in ("ln1", "ln2", "kv_norm", "final_norm"):
                flat[name] = jnp.ones(shape, WEIGHT_DTYPE)
                continue
            std = (0.02 if base == "embed" else BIAS_STD
                   if base == "router_bias"
                   else 1.0 / math.sqrt(_fan_in(name, shape, s)))
            flat[name] = (std * jax.random.normal(k, shape, jnp.float32)
                          ).astype(WEIGHT_DTYPE)
        out: dict = {}
        for name, a in flat.items():
            *groups, base = name.split("/")
            node = out
            for g in groups:
                node = node.setdefault(g, {})
            node[base] = a
        return out

    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(init, out_shardings=sharding)(key)


def _block(w: dict) -> dict:
    return {
        "ln_attn": {"scale": w["ln1"]},
        "attn": {"w_q": w["wq"], "w_dkv": w["w_dkv"],
                 "kv_norm": {"scale": w["kv_norm"]}, "w_kr": w["w_kr"],
                 "w_uk": w["w_uk"], "w_uv": w["w_uv"], "w_o": w["wo"]},
        "ln_mlp": {"scale": w["ln2"]},
    }


def program_params(w: dict) -> dict:
    """The same arrays arranged as the program's parameter tree."""
    d, m = w["dense"], w["moe"]
    return {
        "embed": {"table": w["embed"]},
        "stages": {
            "dense": {"blocks": {**_block(d), "mlp": {
                "wi_gate": d["w_gate"], "wi_up": d["w_up"],
                "wo": d["w_down"]}}},
            "moe": {"blocks": {**_block(m), "moe": {
                "router": m["router"], "router_bias": m["router_bias"],
                "wi_gate": m["w_gate"], "wi_up": m["w_up"],
                "wo": m["w_down"],
                "shared": {"wi_gate": m["shared_gate"],
                           "wi_up": m["shared_up"],
                           "wo": m["shared_down"]}}}},
        },
        "final_norm": {"scale": w["final_norm"]},
        "head": {"w": w["head"]},
        "img_proj": {"w": w["img_proj"]},
    }


def arch_config(s: dict):
    from repro.common.config import ArchConfig

    return ArchConfig(
        name="vlm-head", family="moe", n_layers=s["num_hidden_layers"],
        d_model=s["hidden_size"], n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_attention_heads"], d_ff=s["intermediate_size"],
        vocab_size=s["vocab_size"], head_dim=s["v_head_dim"],
        n_experts=s["router_experts"],
        experts_top_k=s["num_experts_per_tok"],
        n_shared_experts=s["n_shared_experts"],
        moe_d_ff=s["moe_intermediate_size"],
        first_dense_layers=s["first_k_dense_replace"],
        dense_d_ff=s["intermediate_size"],
        router_score="sigmoid", router_bias=True,
        routed_scale=s["routed_scaling_factor"],
        first_held_expert=s["first_held_expert"],
        held_experts=s["n_routed_experts"],
        use_mla=True, q_lora_rank=0, kv_lora_rank=s["kv_lora_rank"],
        qk_rope_dim=s["qk_rope_head_dim"], qk_nope_dim=s["qk_nope_head_dim"],
        v_head_dim=s["v_head_dim"],
        rope_theta=s["rope_theta"], norm_eps=s["rms_norm_eps"],
        tie_embeddings=False, has_vision_stub=True,
        n_image_tokens=s["n_image_tokens"])


def pix_encode(w, x):
    """The vision stub: precomputed patch embeddings through one
    projection (named, so its compiled program is ``jit_pix_encode``)."""
    return jnp.tanh(x @ w)


def kv_bytes_per_token(s: dict) -> int:
    """The latent cache of every layer: c_kv and the rotary key, fp32."""
    return (s["num_hidden_layers"]
            * (s["kv_lora_rank"] + s["qk_rope_head_dim"]) * 4)


def build(part: dict, weights: dict, serve: dict):
    """(model specs, module builders, module roles) for the program."""
    from repro.core.module import ModelSpec, ModuleSpec
    from repro.models.api import build_model

    s = sizes(part)
    bundle = build_model(arch_config(s), compute_dtype=jnp.float32)
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
                     bytes_per_param=2.0, flops_per_query=2.0 * n_img * d * d,
                     input_bytes=4 * n_img * d, output_bytes=4 * n_img * d)
    head = ModuleSpec(
        head_name, "head", "task", n_head, bytes_per_param=2.0,
        generative=True, flops_per_query=2.0 * n_head * serve["max_seq_len"],
        input_bytes=4 * n_img * d, kv_bytes_per_token=kv_bytes_per_token(s))
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
    """Teacher-forced reference over each ``(request, tokens)`` pair, on
    every served token: the gap by which its reference logit lies below
    the reference's best, their mean (``served_gap_mean``), the share of
    tokens whose gap exceeds ``LARGE_GAP`` (``large_gap_share``), the
    widest gap (``served_gap``) and the largest routing margin at which
    a large gap came (``large_gap_margin_max``); with ``control`` the
    same widest, mean and share for the tokens that the fp8 copy of the
    reference (``control_*``) and the bfloat16-operand copy (``bf16_*``)
    put first."""
    s = sizes(part)
    n_img = s["n_image_tokens"]
    T = serve["max_seq_len"] - n_img
    items = tuple(sorted(s.items()))
    cols: dict[str, list] = {}
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
        got = kimi_vl_dec.score(
            weights, req.inputs["vision"], jnp.asarray(seq), jnp.asarray(pos),
            jnp.asarray(tok), jnp.asarray(valid), sizes_items=items,
            control=control)
        for k, v in got.items():
            cols.setdefault(k, []).append(np.asarray(v)[:n])
    c = {k: np.concatenate(v) for k, v in cols.items()}
    large = c["gap"] > LARGE_GAP
    out = {"served_gap_mean": float(c["gap"].mean()),
           "large_gap_share": float(large.mean()),
           "served_gap": float(c["gap"].max()),
           "large_gap_margin_max": float(c["margin"][large].max(initial=0.0)),
           "tokens_compared": int(c["gap"].size)}
    for name in ("control", "bf16"):
        if name in c:
            out[f"{name}_gap"] = float(c[name].max())
            out[f"{name}_gap_mean"] = float(c[name].mean())
            out[f"{name}_large_gap_share"] = float(
                (c[name] > LARGE_GAP).mean())
    return out
