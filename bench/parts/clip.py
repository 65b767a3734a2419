"""A CLIP-style deployment part: one vision tower shared by a zero-shot
classification task and an image-text retrieval task, one text tower,
and the two task heads.

The part's entry in a configuration file gives the published sizes under
``vision_config`` and ``text_config`` (Hugging Face key names), the
projection width, the number of classes and the module names.  This
file builds the program's modules from them, draws every weight on the
device in one jitted call from the seed, makes request payloads, and
checks the heads' answers against ``bench/reference/clip.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import clip as ref_clip

KIND = "encoder"
TEXT_POS_ROWS = 512          # the program's text position table


def sizes(part: dict) -> dict:
    v, t = part["vision_config"], part["text_config"]
    n_img = (v["image_size"] // v["patch_size"]) ** 2
    return {
        "v_width": v["hidden_size"], "v_layers": v["num_hidden_layers"],
        "v_heads": v["num_attention_heads"], "n_image_tokens": n_img,
        "t_width": t["hidden_size"], "t_layers": t["num_hidden_layers"],
        "t_heads": t["num_attention_heads"], "vocab_size": t["vocab_size"],
        "context": t["max_position_embeddings"],
        "embed_dim": part["projection_dim"], "classes": part["classes"],
        "eps": float(v["layer_norm_eps"]),
        "logit_scale": float(part["logit_scale_init_value"]),
    }


def _tower_shapes(W: int, heads: int, L: int) -> dict:
    hd = W // heads
    return {"ln1_s": (L, W), "ln1_b": (L, W), "wq": (L, W, heads, hd),
            "wk": (L, W, heads, hd), "wv": (L, W, heads, hd),
            "wo": (L, heads, hd, W), "ln2_s": (L, W), "ln2_b": (L, W),
            "w_gate": (L, W, 4 * W), "w_up": (L, W, 4 * W),
            "w_down": (L, 4 * W, W), "ln_s": (W,), "ln_b": (W,)}


def weight_shapes(s: dict) -> dict:
    vw, tw, E = s["v_width"], s["t_width"], s["embed_dim"]
    vision = {**_tower_shapes(vw, s["v_heads"], s["v_layers"]),
              "patch_proj": (vw, vw), "pos": (s["n_image_tokens"], vw),
              "proj": (vw, E)}
    text = {**_tower_shapes(tw, s["t_heads"], s["t_layers"]),
            "embed": (s["vocab_size"], tw), "pos": (TEXT_POS_ROWS, tw),
            "proj": (tw, E)}
    return {"vision": vision, "text": text, "cls_w": (E, s["classes"])}


def _fan_in(name: str, shape: tuple) -> int:
    if name == "wo":
        return shape[1] * shape[2]
    return shape[-2] if len(shape) == 2 else shape[1]


def make_weights(s: dict, key, device):
    """Every weight, drawn on ``device`` in one jitted call: fan-in
    scaled normals for matrices, 0.02 for embeddings and position
    tables, ones and zeros for LayerNorms, the published logit scale."""
    shapes = weight_shapes(s)

    def leaf(k, name, shape):
        if name.endswith("_s"):
            return jnp.ones(shape, jnp.float32)
        if name.endswith("_b"):
            return jnp.zeros(shape, jnp.float32)
        std = 0.02 if name in ("embed", "pos") else 1 / math.sqrt(
            _fan_in(name, shape))
        return std * jax.random.normal(k, shape, jnp.float32)

    def init(key):
        out = {}
        for i, tower in enumerate(("vision", "text")):
            tk = jax.random.fold_in(key, i)
            out[tower] = {n: leaf(jax.random.fold_in(tk, j), n, sh)
                          for j, (n, sh) in enumerate(
                              sorted(shapes[tower].items()))}
        out["cls_w"] = leaf(jax.random.fold_in(key, 7), "cls_w",
                            shapes["cls_w"])
        out["logit_scale"] = jnp.asarray(s["logit_scale"], jnp.float32)
        return out

    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(init, out_shardings=sharding)(key)


def _program_tower(w: dict) -> dict:
    return {"ln1": {"scale": w["ln1_s"], "bias": w["ln1_b"]},
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"]},
            "ln2": {"scale": w["ln2_s"], "bias": w["ln2_b"]},
            "mlp": {"wi_gate": w["w_gate"], "wi_up": w["w_up"],
                    "wo": w["w_down"]}}


def program_params(w: dict) -> dict:
    """The same arrays arranged as the program's CLIP parameter tree."""
    v, t = w["vision"], w["text"]
    return {
        "vision": {"patch_proj": v["patch_proj"], "pos": v["pos"],
                   "blocks": _program_tower(v),
                   "ln_post": {"scale": v["ln_s"], "bias": v["ln_b"]},
                   "proj": v["proj"]},
        "text": {"embed": {"table": t["embed"]}, "pos": t["pos"],
                 "blocks": _program_tower(t),
                 "ln_final": {"scale": t["ln_s"], "bias": t["ln_b"]},
                 "proj": t["proj"]},
        "logit_scale": w["logit_scale"],
    }


def clip_config(s: dict):
    from repro.models.clip import ClipConfig

    return ClipConfig(
        name="clip", vision_layers=s["v_layers"], vision_width=s["v_width"],
        vision_heads=s["v_heads"], text_layers=s["t_layers"],
        text_width=s["t_width"], text_heads=s["t_heads"],
        vocab_size=s["vocab_size"], embed_dim=s["embed_dim"],
        n_image_tokens=s["n_image_tokens"], norm_eps=s["eps"])


def build(part: dict, weights: dict, serve: dict):
    """(model specs, module builders, module roles) for the program."""
    from repro.core.module import ModelSpec, ModuleSpec
    from repro.models import clip as C

    s = sizes(part)
    ccfg = clip_config(s)
    params = program_params(weights)
    want = jax.eval_shape(lambda k: C.init_clip(k, ccfg),
                          jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(params) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                               jax.tree.leaves(params))):
        raise ValueError("benchmark weights do not match the program's "
                         "CLIP parameter tree for this configuration")

    def vit_encode(p, x):
        return C.encode_image(p, x, ccfg)

    def text_encode(p, ids):
        return C.encode_text(p, ids, ccfg)

    def retrieval_head(p, enc):
        return C.retrieval_logits(enc["vision"], enc["text"], p)

    def classify_head(p, enc):
        return enc["vision"] @ p

    names = part["modules"]
    shapes = weight_shapes(s)
    nv = sum(math.prod(x) for x in shapes["vision"].values())
    nt = sum(math.prod(x) for x in shapes["text"].values())
    N, vw, S, tw = s["n_image_tokens"], s["v_width"], s["context"], s["t_width"]
    vit = ModuleSpec(names["vision"], "encoder", "vision", nv,
                     bytes_per_param=4.0, flops_per_query=2.0 * nv * N,
                     input_bytes=4 * N * vw, output_bytes=4 * s["embed_dim"])
    txt = ModuleSpec(names["text"], "encoder", "text", nt,
                     bytes_per_param=4.0, flops_per_query=2.0 * nt * S,
                     input_bytes=4 * S, output_bytes=4 * s["embed_dim"])
    cos = ModuleSpec(names["retrieval"], "head", "task", 1,
                     bytes_per_param=4.0)
    cls = ModuleSpec(names["classify"], "head", "task",
                     s["embed_dim"] * s["classes"], bytes_per_param=4.0,
                     flops_per_query=2.0 * s["embed_dim"] * s["classes"])
    builders = {
        names["vision"]: lambda: (vit_encode, params["vision"]),
        names["text"]: lambda: (text_encode, params["text"]),
        names["retrieval"]: lambda: (retrieval_head, params["logit_scale"]),
        names["classify"]: lambda: (classify_head, weights["cls_w"]),
    }
    models = [ModelSpec("retrieval", "retrieval", (vit, txt), cos),
              ModelSpec("classify", "classification", (vit,), cls)]
    roles = {"vit": names["vision"], "text": names["text"],
             "encoders": [names["vision"], names["text"]]}
    return models, builders, roles


def make_pools(part: dict, key, device, pool_size: int) -> dict:
    """Images of patch embeddings (1, patches, width) and caption sets
    (captions, context) of token ids, on the device."""
    s = sizes(part)
    C = part["captions_per_image"]
    sharding = jax.sharding.SingleDeviceSharding(device)

    def draw(k):
        ki, kt = jax.random.split(k)
        img = jax.random.normal(
            ki, (pool_size, 1, s["n_image_tokens"], s["v_width"]),
            jnp.float32)
        ids = jax.random.randint(kt, (pool_size, C, s["context"]), 0,
                                 s["vocab_size"], jnp.int32)
        return img, ids

    img, ids = jax.jit(draw, out_shardings=sharding)(key)
    return {"image": [img[i] for i in range(pool_size)],
            "captions": [ids[i] for i in range(pool_size)]}


def make_request(part: dict, arrival, rid: int, source: str, pools: dict,
                 rng: np.random.Generator):
    from repro.s2m3 import Request

    inputs = {"vision": pools["image"][arrival.payload]}
    if arrival.task == "retrieval":
        inputs["text"] = pools["captions"][arrival.payload]
    return Request(rid, arrival.task, source, inputs=inputs)


def _rel_err(got, want) -> float:
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64).reshape(want.shape)
    scale = max(float(np.sqrt(np.mean(want ** 2))), 1e-30)
    return float(np.max(np.abs(got - want)) / scale)


def check(part: dict, weights: dict, served: list, *, serve: dict,
          control: bool = False, block: int = 8) -> dict:
    """Compare each ``(request, answer)`` with the reference's answer.

    The number per answer is its largest deviation over the reference
    answer's root mean square; the result holds the widest per task
    (``classify_err``, ``retrieval_err``), and with ``control`` the same
    for an fp8 copy of the reference in the program's place.
    """
    s = sizes(part)
    kw = dict(vheads=s["v_heads"], theads=s["t_heads"], eps=s["eps"])
    out = {"answers_compared": len(served)}
    for task, col in (("classify", 0), ("retrieval", 1)):
        items = [(q, a) for q, a in served if q.model == task]
        worst = ctrl = 0.0
        for i in range(0, len(items), block):
            chunk = items[i:i + block]
            patches = jnp.concatenate([q.inputs["vision"] for q, _ in chunk])
            ids = (jnp.stack([q.inputs["text"] for q, _ in chunk])
                   if task == "retrieval" else None)
            want = ref_clip.score(weights, patches, ids, **kw)[col]
            for j, (_, ans) in enumerate(chunk):
                worst = max(worst, _rel_err(ans, want[j]))
            if control:
                low = ref_clip.score(weights, patches, ids, low=True, **kw)[col]
                for j in range(len(chunk)):
                    ctrl = max(ctrl, _rel_err(low[j], want[j]))
        out[f"{task}_err"] = worst
        if control:
            out[f"{task}_control_err"] = ctrl
    return out
