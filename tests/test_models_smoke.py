"""Per-arch REQUIRED smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes and no NaNs; plus decode<->prefill
consistency (the serving contract)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import get_config, list_archs
from repro.models.api import build_model

ARCHS = list_archs()


def _batch(cfg, B=2, S=16, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    text = S - (cfg.n_image_tokens if cfg.has_vision_stub else 0)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, text), 0, cfg.vocab_size),
        "targets": jax.random.randint(ks[1], (B, text), 0, cfg.vocab_size),
        "mask": jnp.ones((B, text), jnp.float32),
    }
    if cfg.has_vision_stub:
        batch["image_embeds"] = 0.1 * jax.random.normal(
            ks[2], (B, cfg.n_image_tokens, cfg.d_model))
    if cfg.is_encoder_decoder:
        batch["audio_frames"] = 0.1 * jax.random.normal(
            ks[3], (B, cfg.encoder_seq, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch)
    expected = {
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 49155),
        "deepseek-v3-671b": (61, 7168, 128, 128, 129280),
        "gemma2-9b": (42, 3584, 16, 8, 256000),
        "llama3-8b": (32, 4096, 32, 8, 128256),
        "tinyllama-1.1b": (22, 2048, 32, 4, 32000),
        "llama3-405b": (126, 16384, 128, 8, 128256),
        "internvl2-1b": (24, 896, 14, 2, 151655),
        "whisper-tiny": (4, 384, 6, 6, 51865),
        "zamba2-7b": (81, 3584, 32, 32, 32000),
        "xlstm-1.3b": (48, 2048, 4, 4, 50304),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.vocab_size)
    assert got == expected, (arch, got, expected)


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = get_config(arch, smoke=True)
    m = build_model(cfg, compute_dtype=jnp.float32)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss, metrics = jax.jit(m.loss_fn)(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), arch
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_grads_finite(arch):
    cfg = get_config(arch, smoke=True)
    m = build_model(cfg, compute_dtype=jnp.float32)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, B=1, S=16)
    g = jax.jit(jax.grad(lambda p: m.loss_fn(p, batch)[0]))(params)
    for path_leaf in jax.tree.leaves(g):
        assert np.isfinite(np.asarray(path_leaf)).all(), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_prefill(arch):
    """Teacher-forcing consistency: decoding token S given cache from a
    prefill of S tokens must equal a fresh prefill over S+1 tokens."""
    cfg = get_config(arch, smoke=True)
    m = build_model(cfg, compute_dtype=jnp.float32)
    params = m.init(jax.random.PRNGKey(0))
    B, S, T = 2, 8, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                              cfg.vocab_size)
    extra = {}
    if cfg.has_vision_stub:
        extra["image_embeds"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(3), (B, cfg.n_image_tokens, cfg.d_model))
    if cfg.is_encoder_decoder:
        extra["audio_frames"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(4), (B, cfg.encoder_seq, cfg.d_model))
    n_pref = cfg.n_image_tokens if cfg.has_vision_stub else 0

    cache = m.init_cache(B, T, dtype=jnp.float32)
    _, cache, _ = jax.jit(m.prefill)(params, {"tokens": toks[:, :S], **extra},
                                     cache)
    lengths = jnp.full((B,), S + n_pref, jnp.int32)
    logits, _ = jax.jit(m.decode_step)(params, toks[:, S:], cache, lengths)

    cache2 = m.init_cache(B, T, dtype=jnp.float32)
    logits_ref, _, _ = jax.jit(m.prefill)(
        params, {"tokens": toks[:, : S + 1], **extra}, cache2)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_ref),
                               rtol=5e-4, atol=5e-4)


def test_moe_active_params_less_than_total():
    for arch in ("granite-moe-3b-a800m", "deepseek-v3-671b"):
        m = build_model(get_config(arch, smoke=True))
        assert m.active_param_count() < m.param_count()


@pytest.mark.parametrize("arch", ["internvl2-1b", "whisper-tiny"])
def test_build_model_takes_only_its_named_options(arch):
    """The step's paths follow from its mode and the mesh: a removed
    option is refused, not ignored, and the named ones still build."""
    cfg = get_config(arch, smoke=True)
    with pytest.raises(TypeError):
        build_model(cfg, paged_attn="xla")
    m = build_model(cfg, compute_dtype=jnp.float32, remat="dots")
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    loss, _ = jax.eval_shape(m.loss_fn, params, _batch(cfg))
    assert loss.shape == () and loss.dtype == jnp.float32
