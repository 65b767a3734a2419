"""Compile the smoke's full-width programs for a described TPU v5e chip.

internvl2-1b at its published widths, as ``chip_smoke.py`` serves it
(float32, XLA paged decode), compiled for one v5e chip from abstract
shapes: the TPU compiler refuses here what the chip would refuse, at no
chip time.  Nothing runs, so these say nothing about results or speed.

The topology is described inside a module fixture and never at import:
only one process may load libtpu, and test workers import every file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.common.config import get_config
from repro.common.profiling import memory_summary
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
