"""Matmul weights held as the MXU reads them.

On the TPU a default-precision float32 matmul is one bfloat16 pass, so
the compiler rounds every float32 weight operand to bfloat16 in every
step.  ``round_mxu_operands`` makes that rounding once, when the engine
deploys a decoder: every float32 leaf whose layer declares it a matmul
operand (``WSpec.mxu_operand``) becomes its bfloat16 rounding, and every
other leaf passes through as the same array.  The platform and the
matmul precision are given here as the TPU's; on the CPU nothing moves.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.layers.initializers as init_lib
from repro.common.config import get_config
from repro.core.module import ModelSpec, ModuleSpec
from repro.core.routing import Request
from repro.layers.initializers import mxu_rounds_f32, round_mxu_operands
from repro.models.api import build_model
from repro.serving.engine import S2M3Engine
from repro.serving.scheduler import SchedulerConfig, ServeScheduler

TPU = dict(platform="tpu", precision=None)
# Qwen2-shaped (internvl2-1b: GQA, SwiGLU, tied embedding, image
# projection) and an MLA + MoE tree with a sigmoid router and its bias
QWEN = "internvl2-1b"
MOE = "deepseek-v3-671b"


def _bundle(arch):
    cfg = get_config(arch, smoke=True)
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, router_score="sigmoid",
                                  router_bias=True)
    return build_model(cfg, compute_dtype=jnp.float32)


def _paths(tree):
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_leaves_with_path(tree)}


def _rounded_names(bundle):
    return {k for k, ws in _paths(jax.tree.map(
        lambda ws: ws.mxu_operand, bundle.specs,
        is_leaf=lambda x: isinstance(x, init_lib.WSpec))).items() if ws}


def test_declared_matmul_leaves_round_to_bf16():
    b = _bundle(QWEN)
    params = b.init(jax.random.PRNGKey(0))
    out, nbytes = round_mxu_operands(params, b.specs, **TPU)
    before, after = _paths(params), _paths(out)
    rounded = _rounded_names(b)
    blocks = "['stages']['blocks']['blocks']"
    assert rounded == {
        f"{blocks}['attn']['{w}']" for w in ("wq", "wk", "wv", "wo")
    } | {f"{blocks}['mlp']['{w}']" for w in ("wi_gate", "wi_up", "wo")
         } | {"['img_proj']['w']"}
    for name, x in before.items():
        if name in rounded:
            assert after[name].dtype == jnp.bfloat16, name
            np.testing.assert_array_equal(
                np.asarray(after[name]), np.asarray(x.astype(jnp.bfloat16)))
        else:
            assert after[name] is x, name     # norms, tied embedding table
    assert nbytes == sum(before[n].size * 2 for n in rounded)


def test_router_norms_and_embedding_pass_through_a_moe_tree():
    b = _bundle(MOE)
    params = b.init(jax.random.PRNGKey(0))
    out, nbytes = round_mxu_operands(params, b.specs, **TPU)
    before, after = _paths(params), _paths(out)
    kept = [n for n in before
            if any(k in n for k in ("'router'", "'router_bias'", "norm",
                                    "'embed'"))]
    assert any("'router_bias'" in n for n in kept)
    for name in kept:
        assert after[name] is before[name], name
    experts = [n for n in before if "['moe']['w" in n]
    mla = [n for n in before if "['attn']['w_" in n]
    assert experts and mla
    for name in experts + mla:
        assert after[name].dtype == jnp.bfloat16, name
    assert nbytes == sum(before[n].size * 2 for n in _rounded_names(b))


def test_a_bfloat16_tree_comes_back_leaf_for_leaf():
    """Kimi's weights are stored in bfloat16: nothing is made."""
    b = _bundle(MOE)
    params = b.init(jax.random.PRNGKey(0), jnp.bfloat16)
    out, nbytes = round_mxu_operands(params, b.specs, **TPU)
    assert nbytes == 0
    assert all(x is y for x, y in zip(jax.tree.leaves(params),
                                      jax.tree.leaves(out)))


@pytest.mark.parametrize("platform,precision,rounds", [
    ("tpu", None, True), ("tpu", "default", True), ("tpu", "bfloat16", True),
    ("cpu", None, False), ("gpu", None, False), ("tpu", "highest", False),
    ("tpu", "high", False), ("tpu", "float32", False)])
def test_matmul_precision_decides_the_rounding(platform, precision, rounds):
    """Only one bfloat16 pass rounds; where the matmul is exact or takes
    more passes the tree comes back as it is."""
    b = _bundle(QWEN)
    params = b.init(jax.random.PRNGKey(0))
    assert mxu_rounds_f32(platform, precision) == rounds
    out, nbytes = round_mxu_operands(params, b.specs, platform=platform,
                                     precision=precision)
    assert (out is params and nbytes == 0) != rounds


@pytest.mark.parametrize("arch", [QWEN, MOE])
def test_rounded_tree_computes_as_its_float32_values(arch):
    """The paged decode step and the prefill read a bfloat16 leaf as the
    float32 of its value: a float32 tree holding the same rounded values
    gives the same logits and cache."""
    b = _bundle(arch)
    cfg = b.cfg
    rounded, _ = round_mxu_operands(b.init(jax.random.PRNGKey(0)), b.specs,
                                    **TPU)
    widened = jax.tree.map(lambda x: x.astype(jnp.float32), rounded)
    assert {x.dtype for x in jax.tree.leaves(rounded)} == {
        jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)}

    P = 5
    batch = {"tokens": jnp.arange(1, P + 1, dtype=jnp.int32)[None]}
    if cfg.has_vision_stub:
        batch["image_embeds"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(1), (1, cfg.n_image_tokens, cfg.d_model))
    cache = b.init_cache(1, 32, jnp.float32)
    prefill = jax.jit(b.prefill)
    outs = [prefill(p, batch, cache)[:2] for p in (rounded, widened)]
    for x, y in zip(*(jax.tree.leaves(o) for o in outs)):
        np.testing.assert_allclose(x, y, rtol=1e-6, atol=1e-6)

    rows, ps, n_max = 3, 4, 4
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [0, 0, 0, 0]],
                         jnp.int32)
    lengths = jnp.asarray([3, 6, 0], jnp.int32)
    tokens = jnp.asarray([[7], [9], [0]], jnp.int32)
    step = jax.jit(b.paged_decode_step)
    outs = []
    for p in (rounded, widened):
        pool = b.init_paged_cache(1 + rows * n_max, ps, jnp.float32)
        outs.append(step(p, tokens, pool, tables, lengths)[:2])
    for x, y in zip(*(jax.tree.leaves(o) for o in outs)):
        np.testing.assert_allclose(x, y, rtol=1e-6, atol=1e-6)


def _serve(bundle, params):
    head = ModuleSpec(bundle.cfg.name, "head", "task", bundle.param_count(),
                      generative=True)
    engine = S2M3Engine()
    engine.deploy_model(ModelSpec("lm", "generation", (), head),
                        {head.name: lambda: (bundle, params)})
    sched = ServeScheduler(engine, config=SchedulerConfig(
        decode_rows=2, page_size=8, max_seq_len=48, decode_pages=13))
    img = 0.1 * np.random.default_rng(0).standard_normal(
        (bundle.cfg.n_image_tokens, bundle.cfg.d_model)).astype(np.float32)
    reqs = [Request(rid=i, model="lm", source="dev0", prompt=(1, 2 + i),
                    max_new_tokens=5, inputs={"vision": img})
            for i in range(3)]
    outs = [list(r.output) for r in sched.serve(reqs)]
    return engine.decoders[head.name], sched, outs


def test_engine_rounds_at_deploy_and_the_gauge_reads_the_bytes(monkeypatch):
    """On the CPU the deployed head keeps float32 and the gauge reads 0;
    with the TPU's rounding the head's declared leaves are bfloat16, the
    gauge reads their bytes, and the served tokens are those of a
    float32 tree holding the rounded values."""
    b = _bundle(QWEN)
    params = b.init(jax.random.PRNGKey(0))
    rt, sched, _ = _serve(b, params)
    assert rt.mxu_rounded_bytes == 0
    assert sched.stats_dict()[b.cfg.name]["mxu_rounded_bytes"] == 0
    assert {x.dtype for x in jax.tree.leaves(rt.params)} == {
        jnp.dtype(jnp.float32)}

    expect, nbytes = round_mxu_operands(params, b.specs, **TPU)
    widened = jax.tree.map(lambda x: x.astype(jnp.float32), expect)
    _, _, want = _serve(b, widened)
    monkeypatch.setattr(init_lib, "mxu_rounds_f32", lambda *a: True)
    rt, sched, got = _serve(b, params)
    assert nbytes > 0 and rt.mxu_rounded_bytes == nbytes
    assert sched.stats_dict()[b.cfg.name]["mxu_rounded_bytes"] == nbytes
    assert sched.metrics.value("decode.mxu_rounded_bytes",
                               module=b.cfg.name) == nbytes
    for x, y in zip(jax.tree.leaves(rt.params), jax.tree.leaves(expect)):
        assert x.dtype == y.dtype
    assert got == want
