"""Compile the smoke's full-width programs for a described TPU v5e chip.

internvl2-1b at its published widths, as ``chip_smoke.py`` serves it
(float32, XLA paged decode), compiled for one v5e chip from abstract
shapes: the TPU compiler refuses here what the chip would refuse, at no
chip time.  Nothing runs, so these say nothing about results or speed.

The topology is described inside a module fixture and never at import:
only one process may load libtpu, and test workers import every file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.common.config import ArchConfig, get_config
from repro.common.profiling import memory_summary
from repro.layers.initializers import round_mxu_operands
from repro.models.api import build_model

V5E_HBM = 16 * 1024**3
PAGE = chip_smoke.SERVE_KW["page_size"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU executable written to the persistent cache cannot be read
    # back without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def bundle():
    return build_model(get_config(chip_smoke.ARCH), compute_dtype=jnp.float32)


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _params(bundle, chip):
    return _on(bundle.abstract_params(jnp.float32), chip)


def _i32(chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)


def _assert_fits(compiled):
    mem = memory_summary(compiled)
    assert 0 < mem["total_bytes"] < V5E_HBM, mem


def test_paged_decode_step_compiles(bundle, one_chip):
    kw = chip_smoke.SERVE_KW
    rows, n_max = kw["decode_rows"], -(-kw["max_seq_len"] // PAGE)
    cache = _on(jax.eval_shape(lambda: bundle.init_paged_cache(
        kw["decode_pages"], PAGE, jnp.float32)), one_chip)
    step = jax.jit(bundle.paged_decode_step, donate_argnums=(2,))
    compiled = step.lower(_params(bundle, one_chip), _i32(one_chip, rows, 1),
                          cache, _i32(one_chip, rows, n_max),
                          _i32(one_chip, rows)).compile()
    _assert_fits(compiled)


@pytest.mark.parametrize("prompt_len", chip_smoke.PROMPT_LENS)
def test_prefill_compiles(bundle, one_chip, prompt_len):
    cfg = bundle.cfg
    # DecodeStream prefills into a dense cache spanning the owned pages
    span = -(-(cfg.n_image_tokens + prompt_len) // PAGE) * PAGE
    batch = {"tokens": _i32(one_chip, 1, prompt_len),
             "image_embeds": jax.ShapeDtypeStruct(
                 (1, cfg.n_image_tokens, cfg.d_model), jnp.float32,
                 sharding=one_chip)}
    cache = _on(jax.eval_shape(
        lambda: bundle.init_cache(1, span, jnp.float32)), one_chip)
    compiled = jax.jit(bundle.prefill).lower(
        _params(bundle, one_chip), batch, cache).compile()
    _assert_fits(compiled)


def test_solo_decode_step_compiles(bundle, one_chip):
    """The dense batch-1 step behind ``dep.submit()``, the smoke's
    token-exact reference, at its longest cache."""
    cfg = bundle.cfg
    total = (cfg.n_image_tokens + max(chip_smoke.PROMPT_LENS)
             + chip_smoke.MAX_NEW + 1)
    cache = _on(jax.eval_shape(
        lambda: bundle.init_cache(1, -(-total // 8) * 8, jnp.float32)),
        one_chip)
    step = jax.jit(bundle.decode_step, donate_argnums=(2,))
    compiled = step.lower(_params(bundle, one_chip), _i32(one_chip, 1, 1),
                          cache, _i32(one_chip, 1)).compile()
    _assert_fits(compiled)


# The paged decode step's pool traffic at the served cells' shapes, cut
# to three layers: Qwen2-0.5B's attention (the vlm-mt cells: 32 rows, 30
# pages of 16 a row, 961 pages) and Kimi-VL-A3B's MLA + MoE decoder with
# one dense and two routed layers (kimi-mt.docs: 64 rows, 82 pages a
# row, 5249 pages; 16 of 64 experts held, weights in bfloat16).
KIMI = ArchConfig(
    name="kimi-vl-a3b", family="moe", n_layers=3, d_model=2048, n_heads=16,
    n_kv_heads=16, d_ff=11264, vocab_size=163840, head_dim=128,
    n_experts=64, experts_top_k=6, n_shared_experts=2, moe_d_ff=1408,
    first_dense_layers=1, dense_d_ff=11264, router_score="sigmoid",
    router_bias=True, routed_scale=2.446, held_experts=16, use_mla=True,
    q_lora_rank=0, kv_lora_rank=512, qk_rope_dim=64, qk_nope_dim=128,
    v_head_dim=128, rope_theta=800000.0, norm_eps=1e-5,
    tie_embeddings=False, has_vision_stub=True, n_image_tokens=1024)
POOL_CELLS = {
    "attention": (lambda: dataclasses.replace(
        get_config(chip_smoke.ARCH), n_layers=3), jnp.float32, 32, 30, 961),
    "mla_moe": (lambda: KIMI, jnp.bfloat16, 64, 82, 5249),
}
# an instruction's result shape and opcode: "%x = f32[...]{...} copy("
_INSTR = re.compile(r"%\S+ = (.+?) ([a-z][a-z0-9-]*)\(")
_MOVES = {"copy", "copy-start", "copy-done", "dynamic-slice",
          "dynamic-update-slice"}


def pool_sized_moves(hlo: str, n_pages: int) -> list[str]:
    """Instructions, fused ones included, that copy, slice, update-slice
    or freshly allocate a buffer with the pool's page axis."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.search(line)
        if m is None:
            continue
        shape, op = m.groups()
        if not (op in _MOVES or 'custom_call_target="AllocateBuffer"' in line):
            continue
        dims = re.findall(r"\[([\d,]*)\]", shape)
        if any(str(n_pages) in d.split(",") for d in dims):
            out.append(line.strip()[:160])
    return out


def test_pool_sized_moves_finds_a_scanned_pool_copy():
    """The check reads the compiled text as the TPU compiler writes it."""
    hlo = """
  %copy.81 = f32[24,961,16,2,64]{1,4,3,2,0:T(8,128)} copy(%get-tuple-element.969)
  %copy-start.8 = (f32[1,961,16,128]{3,2,1,0:T(8,128)S(1)}, f32[1,961,16,128]{3,2,1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%c)
  ROOT %dynamic_slice.169 = f32[1,961,16,2,64]{1,4,3,2,0:T(8,128)S(1)} dynamic-slice(%p, %q)
  %custom-call.15 = f32[24,961,16,128]{3,2,1,0:T(8,128)} custom-call(), custom_call_target="AllocateBuffer"
  ROOT %scatter.29 = f32[24,961,16,128]{3,2,1,0:T(8,128)} scatter(%p, %i, %u)
  %copy.24 = f32[32,480,2,64]{1,3,2,0:T(8,128)S(1)} copy(%reshape.358)
"""
    assert len(pool_sized_moves(hlo, 961)) == 4
    assert pool_sized_moves(hlo, 962) == []


def _compile_paged_step(cell, chip, n_pages):
    make_cfg, weights, rows, n_max, _ = POOL_CELLS[cell]
    b = build_model(make_cfg(), compute_dtype=jnp.float32)
    cache = _on(jax.eval_shape(
        lambda: b.init_paged_cache(n_pages, PAGE, jnp.float32)), chip)
    step = jax.jit(b.paged_decode_step, donate_argnums=(2,))
    return step.lower(_on(b.abstract_params(weights), chip),
                      _i32(chip, rows, 1), cache, _i32(chip, rows, n_max),
                      _i32(chip, rows)).compile()


@pytest.mark.parametrize("cell", sorted(POOL_CELLS))
def test_paged_decode_step_writes_the_pool_in_place(cell, one_chip):
    """With the cache donated, only the scatter of the new rows and the
    gather that reads the pages touch the pool: no instruction copies,
    slices, update-slices or allocates a buffer with its page axis, and
    the temporaries do not grow when the pool doubles."""
    n_pages = POOL_CELLS[cell][-1]
    temps = []
    for n in (n_pages, 2 * n_pages):
        compiled = _compile_paged_step(cell, one_chip, n)
        assert pool_sized_moves(compiled.as_text(), n) == []
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
    assert temps[1] <= temps[0], temps


# Float32 matmul weights at the vlm-mt cells' shapes, full depth: 24
# layers, 32 rows, 30 pages of 16 a row, 961 pages, a 20-token prompt
# behind the 256-token image prefix.  At default precision the TPU reads
# a float32 operand as bfloat16, so with float32 weights the compiler
# converts each whole stacked weight in every run of a program; the
# engine deploys the declared matmul weights already rounded
# (``round_mxu_operands``), and the programs then convert none.
VLM_ROWS, VLM_N_MAX, VLM_PAGES, VLM_PROMPT = 32, 30, 961, 20


def stacked_weight_converts(hlo: str, shapes) -> list[str]:
    """Instructions, fused ones included, that convert to a buffer with
    one of ``shapes``, the stacked (per-layer) weights' shapes."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.search(line)
        if m is None or m.group(2) != "convert":
            continue
        dims = re.findall(r"\[([\d,]+)\]", m.group(1))
        if any(tuple(map(int, d.split(","))) in shapes for d in dims):
            out.append(line.strip()[:160])
    return out


def test_stacked_weight_converts_finds_a_hoisted_weight_convert():
    """The check reads the compiled text as the TPU compiler writes it."""
    hlo = """
  %convert.10 = bf16[24,896,4864]{2,1,0:T(8,128)(2,1)} convert(%params__stages____blocks____blocks____mlp____wi_gate__.1), backend_config={}
  %convert.6 = bf16[24,896,14,64]{1,3,2,0:T(8,128)(2,1)} convert(%custom-call.24)
  ROOT %convert.29 = bf16[32,4864]{1,0:T(8,128)(2,1)} convert(%mul.585)
  %fusion.106 = f32[32,151655]{1,0:T(8,128)} fusion(%p, %q), kind=kOutput
  %copy.3 = bf16[24,896,4864]{2,1,0:T(8,128)(2,1)} copy(%convert.10)
"""
    shapes = {(24, 896, 4864), (24, 896, 14, 64)}
    assert len(stacked_weight_converts(hlo, shapes)) == 2
    assert stacked_weight_converts(hlo, {(24, 4864, 896)}) == []


def _vlm_programs(b, chip, params):
    """The compiled paged decode step and prefill of the vlm-mt decoder
    ``b`` with ``params`` (abstract, on ``chip``)."""
    cfg = b.cfg
    pool = _on(jax.eval_shape(
        lambda: b.init_paged_cache(VLM_PAGES, PAGE, jnp.float32)), chip)
    step = jax.jit(b.paged_decode_step, donate_argnums=(2,)).lower(
        params, _i32(chip, VLM_ROWS, 1), pool,
        _i32(chip, VLM_ROWS, VLM_N_MAX), _i32(chip, VLM_ROWS)).compile()
    span = -(-(cfg.n_image_tokens + VLM_PROMPT) // PAGE) * PAGE
    batch = {"tokens": _i32(chip, 1, VLM_PROMPT),
             "image_embeds": jax.ShapeDtypeStruct(
                 (1, cfg.n_image_tokens, cfg.d_model), jnp.float32,
                 sharding=chip)}
    cache = _on(jax.eval_shape(
        lambda: b.init_cache(1, span, jnp.float32)), chip)
    prefill = jax.jit(b.prefill).lower(params, batch, cache).compile()
    return {"paged_decode_step": step, "prefill": prefill}


@pytest.fixture(scope="module")
def vlm_weight_programs(bundle, one_chip):
    """Both programs with the float32 tree and with the tree the engine
    deploys on the TPU, and the stacked weights' shapes."""
    f32 = bundle.abstract_params(jnp.float32)
    deployed = jax.eval_shape(lambda p: round_mxu_operands(
        p, bundle.specs, platform="tpu", precision=None)[0], f32)
    blocks = f32["stages"]["blocks"]["blocks"]
    shapes = {tuple(x.shape) for x in jax.tree.leaves(blocks)}
    return (_vlm_programs(bundle, one_chip, _on(f32, one_chip)),
            _vlm_programs(bundle, one_chip, _on(deployed, one_chip)), shapes)


@pytest.mark.parametrize("program", ["paged_decode_step", "prefill"])
def test_deployed_weights_leave_no_weight_convert(vlm_weight_programs,
                                                  program):
    """With float32 weights each of the seven stacked matmul weights
    (wq, wk, wv, wo; wi_gate, wi_up, wo) is converted whole; with the
    deployed tree none is, and the temporaries shrink."""
    f32, deployed, shapes = vlm_weight_programs
    found = stacked_weight_converts(f32[program].as_text(), shapes)
    assert len(found) == 7, found
    assert stacked_weight_converts(deployed[program].as_text(), shapes) == []
    temps = [p[program].memory_analysis().temp_size_in_bytes
             for p in (f32, deployed)]
    assert temps[1] < temps[0], temps
