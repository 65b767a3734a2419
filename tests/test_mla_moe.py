"""The MLA + held-expert MoE decoder (Kimi-VL-A3B's DeepSeek-V3 block) on
the served path, at small sizes on the CPU with seeded random weights,
against the plain reference ``bench/reference/kimi_vl_dec.py``; and the
bundles of the other families, unchanged.

Tolerances: program and reference both compute in float32 here (the
CPU's default matmul precision is full float32), so they differ only by
summation order (absorbed against expanded attention, masked against
per-expert experts): at most 3.4e-6 of logits up to 4 in size.
``LOGIT_ATOL`` = 3e-5 sits ten times above that.  Routing in bfloat16
moves the served logits by 6.9e-4, twenty times above it; leaving out
the ``q_rope . k_rope`` term moves them by 3.5."""

import importlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import ArchConfig, get_config
from repro.core.routing import Request
from repro.layers import mla as mla_lib
from repro.layers import moe as moe_lib
from repro.layers.initializers import init_tree
from repro.models.api import build_model
from repro.serving.decode import DecodeStream
from repro.serving.scheduler import lm_scheduler

BENCH = Path(__file__).resolve().parents[1] / "bench"
LOGIT_ATOL = 3e-5

SMOKE = {"hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
         "intermediate_size": 128, "moe_intermediate_size": 32,
         "n_routed_experts": 4, "router_experts": 16, "first_held_expert": 4,
         "num_experts_per_tok": 3, "n_shared_experts": 2,
         "first_k_dense_replace": 1, "kv_lora_rank": 16,
         "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
         "routed_scaling_factor": 2.446, "rope_theta": 800000.0,
         "rms_norm_eps": 1e-5, "vocab_size": 256, "n_image_tokens": 6}


@pytest.fixture(scope="module")
def bench_modules():
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    return (importlib.import_module("parts.mla_moe_vlm"),
            importlib.import_module("reference.kimi_vl_dec"))


@pytest.fixture(scope="module")
def served(bench_modules):
    """Smoke-size weights, the program's bundle over them, and the
    reference's logits function."""
    part, ref = bench_modules
    w = part.make_weights(SMOKE, jax.random.PRNGKey(3), jax.devices()[0])
    bundle = build_model(part.arch_config(SMOKE), compute_dtype=jnp.float32)
    return part, ref, w, bundle, part.program_params(w)


def _ref_logits(ref, w, image, prompt, toks, sizes=SMOKE):
    """Reference logits at the positions that predicted each of toks."""
    n_img = sizes["n_image_tokens"]
    seq = jnp.asarray(list(prompt) + list(toks[:-1]), jnp.int32)
    pos = n_img + len(prompt) - 1 + np.arange(len(toks))
    return np.asarray(ref.logits_at(w, image, seq, jnp.asarray(pos),
                                    sizes=sizes))


def _serve_and_compare(ref, w, bundle, params, stream_kw, prompts, new,
                       sizes=SMOKE):
    """Serve ``prompts`` through a ``DecodeStream`` (one image each),
    recording the logits of the prefill and of every paged decode step
    per request, and compare each with the reference's."""
    engine = lm_scheduler(bundle, params).engine
    stream = DecodeStream(engine, bundle.cfg.name, **stream_kw)
    logits = {}
    real_prefill, real_decode = engine.apply_prefill, engine.apply_paged_decode

    def prefill(module, batch, cache):
        out = real_prefill(module, batch, cache)
        key = tuple(np.asarray(batch["tokens"])[0].tolist())
        logits[key] = [np.asarray(out[0])[0]]
        return out

    def decode(module, tokens, cache, tables, lengths):
        out = real_decode(module, tokens, cache, tables, lengths)
        for row, seq in stream.live.items():
            logits[tuple(seq.request.prompt)].append(np.asarray(out[0])[row])
        return out

    engine.apply_prefill, engine.apply_paged_decode = prefill, decode
    img = jax.random.normal(jax.random.PRNGKey(7), (sizes["n_image_tokens"],
                                                    sizes["hidden_size"]))
    vision = jnp.tanh(img @ w["enc_w"])
    for i, (p, n) in enumerate(zip(prompts, new)):
        stream.submit(i, Request(i, "lm", "dev0", prompt=p, max_new_tokens=n),
                      {"vision": vision})
    done = {}
    while stream.depth():
        done.update({s.rid: s.tokens for s in stream.tick().finished})
    assert stream.decode_steps > 0
    for i, p in enumerate(prompts):
        got = np.stack(logits[p])
        want = _ref_logits(ref, w, img, p, done[i], sizes)
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)


def test_prefill_then_paged_decode_matches_reference(served):
    """Prefill, then paged decode through ``DecodeStream`` with three
    rows of different lengths: every logit the program computes agrees
    with the reference's full forward pass."""
    _, ref, w, bundle, params = served
    _serve_and_compare(
        ref, w, bundle, params,
        dict(rows=4, n_pages=25, page_size=4, max_seq_len=32),
        [(5, 9, 2), (17, 3, 40, 41, 8), (77,)], (7, 4, 9))


def test_long_prefix_paged_decode_matches_reference(served):
    """The same at ``kimi-mt.docs``'s lengths: a 1024-token image prefix,
    pages of 16 and ``max_seq_len`` 1312, so each row's latent cache
    spans some 66 pages and the absorbed decode attends over 1,050
    positions; every logit of every row and step agrees with the
    reference's, so no row, page or position goes astray."""
    part, ref, w, _, _ = served
    long = {**SMOKE, "n_image_tokens": 1024}
    bundle = build_model(part.arch_config(long), compute_dtype=jnp.float32)
    _serve_and_compare(
        ref, w, bundle, part.program_params(w),
        dict(rows=4, n_pages=4 * 82 + 1, page_size=16, max_seq_len=1312),
        [tuple(range(3, 11)), tuple(range(40, 63)), tuple(range(90, 105))],
        (20, 12, 17), long)


def test_decode_counts_held_expert_tokens(served):
    """Each step returns the live rows' tokens per (MoE layer, held
    expert), copied with the pick; the stream's counters and tags add
    them up."""
    _, _, _, bundle, params = served
    sched = lm_scheduler(bundle, params)
    stream_name = bundle.cfg.name
    reqs = [Request(i, "lm", "dev0", prompt=(3 + i, 4), max_new_tokens=5,
                    inputs={"vision": np.zeros((6, 64), np.float32)})
            for i in range(3)]
    sched.serve(reqs)
    m = sched.metrics
    k, n_moe = SMOKE["num_experts_per_tok"], 2
    # 3 requests x 4 decode tokens, each picking k experts per MoE layer,
    # some of them held
    local = m.total("moe.local_pairs")
    assert 0 < local <= 3 * 4 * k * n_moe
    per_expert = [i for i in m.instruments("moe.expert_tokens")]
    assert sum(i.value for i in per_expert) == local
    assert {int(i.labels["expert"]) for i in per_expert} <= set(range(4, 8))
    ticks = [s for s in sched.tracer.trace.spans if s.phase == "decode_tick"]
    by_tick = {(s.t0, s.t1): s.attrs for s in ticks}
    assert sum(a["local_pairs"] for a in by_tick.values()) == local
    assert all(a["experts_touched"] <= n_moe * 4 for a in by_tick.values())
    pre = [s for s in sched.tracer.trace.spans if s.phase == "prefill"]
    assert len(pre) == 3 and all("experts_touched" in s.attrs for s in pre)
    assert stream_name in sched.decode


def test_absorbed_paged_decode_matches_expanded(served):
    """Latent-space (absorbed) decode attention equals the expanded form
    over the same cache, masked at each row's length."""
    _, _, _, bundle, params = served
    cfg = bundle.cfg
    p = jax.tree.map(lambda a: a[0], params["stages"]["moe"]["blocks"]["attn"])
    B, T = 3, 12
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(ks[0], (B, 1, cfg.d_model))
    ckv = jax.random.normal(ks[1], (B, T, cfg.kv_lora_rank))
    kr = jax.random.normal(ks[2], (B, T, cfg.qk_rope_dim))
    lengths = jnp.asarray([2, 7, 11])
    pos = lengths[:, None]
    kv_pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    valid = kv_pos < (lengths + 1)[:, None]
    want = mla_lib.mla_attend(p, x, positions=pos, cfg=cfg, ckv_all=ckv,
                              kr_all=kr, kv_positions=kv_pos, kv_valid=valid)
    got = mla_lib.mla_attend_absorbed(p, x, positions=pos, cfg=cfg,
                                      ckv_all=ckv, kr_all=kr, kv_valid=valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)


def _moe_cfg(**kw):
    base = dict(name="m", family="moe", n_layers=1, d_model=16, n_heads=2,
                n_kv_heads=2, d_ff=32, vocab_size=32, n_experts=16,
                experts_top_k=3, moe_d_ff=8, n_shared_experts=2,
                router_score="sigmoid", router_bias=True, routed_scale=2.446)
    return ArchConfig(**{**base, **kw})


def test_four_shares_sum_to_the_uncut_layer(bench_modules):
    """Each of four chips holds 4 of 16 experts: their outputs, with the
    shared experts (computed on every chip alike) counted once, add up
    to the uncut layer, program and reference alike."""
    _, ref = bench_modules
    full = _moe_cfg()
    p = init_tree(jax.random.PRNGKey(0), moe_lib.moe_specs(full))
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 16))
    whole, _ = moe_lib.moe_apply_dense(p, x, full)
    shared = moe_lib.mlp_apply(p["shared"], x, full.act_fn)
    parts = []
    for c in range(4):
        cfg = _moe_cfg(first_held_expert=4 * c, held_experts=4)
        sl = slice(4 * c, 4 * c + 4)
        pc = {**p, "wi_gate": p["wi_gate"][sl], "wi_up": p["wi_up"][sl],
              "wo": p["wo"][sl]}
        parts.append(moe_lib.moe_apply_dense(pc, x, cfg)[0])
    total = sum(parts) - 3 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)
    s = {"num_experts_per_tok": 3, "routed_scaling_factor": 2.446}
    rw = {"router": p["router"], "router_bias": p["router_bias"],
          "w_gate": p["wi_gate"], "w_up": p["wi_up"], "w_down": p["wo"],
          "shared_gate": p["shared"]["wi_gate"],
          "shared_up": p["shared"]["wi_up"], "shared_down": p["shared"]["wo"]}
    mm = lambda spec, a, b: jnp.einsum(                      # noqa: E731
        spec, a, b, precision=jax.lax.Precision.HIGHEST)
    uncut, _ = ref._moe(x.reshape(-1, 16), rw, s=s, first=0, mm=mm, rmm=mm)
    np.testing.assert_allclose(np.asarray(total).reshape(-1, 16),
                               np.asarray(uncut), atol=1e-5, rtol=1e-5)


def test_sigmoid_bias_moves_selection_not_gates():
    """The correction bias picks other experts, and the gates stay the
    chosen experts' own sigmoid scores, normalised and scaled."""
    cfg = _moe_cfg(n_experts=6, experts_top_k=2)
    tokens = jnp.eye(4, 16)
    router = jnp.zeros((16, 6)).at[0, :].set(
        jnp.asarray([3.0, 2.0, 1.0, 0.5, 0.0, -1.0]))
    g0, i0, _ = moe_lib._route(tokens, router, cfg, jnp.zeros((6,)))
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 2.0, 0.0])
    g1, i1, _ = moe_lib._route(tokens, router, cfg, bias)
    assert sorted(np.asarray(i0[0]).tolist()) == [0, 1]
    assert sorted(np.asarray(i1[0]).tolist()) == [0, 4]
    sig = jax.nn.sigmoid(jnp.asarray([3.0, 0.0]))
    np.testing.assert_allclose(np.sort(np.asarray(g1[0])),
                               np.sort(np.asarray(sig / sig.sum() * 2.446)),
                               rtol=1e-6)
    # with no bias the softmax default is today's routing
    soft = ArchConfig(name="m", family="moe", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=32,
                      n_experts=6, experts_top_k=2)
    g2, i2, _ = moe_lib._route(tokens, router, soft)
    pr = jax.nn.softmax(jnp.asarray([3.0, 2.0, 1.0, 0.5, 0.0, -1.0]))
    np.testing.assert_allclose(np.asarray(g2[0]),
                               np.asarray(pr[:2] / pr[:2].sum()), rtol=1e-6)


@pytest.mark.parametrize("expert,held", [(5, True), (1, False)])
def test_masked_held_experts_when_every_token_picks_one(expert, held):
    """With top-1 routing and every token sent to one expert, the share
    returns that expert's SwiGLU times the routed scale (or nothing, if
    another chip holds it) plus the shared experts, and counts every
    valid token on it."""
    cfg = _moe_cfg(experts_top_k=1, first_held_expert=4, held_experts=4)
    p = init_tree(jax.random.PRNGKey(0), moe_lib.moe_specs(cfg))
    p["router"] = jnp.zeros_like(p["router"])
    p["router_bias"] = jnp.zeros((16,)).at[expert].set(1.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 16))
    valid = jnp.arange(5)[None, :] < jnp.asarray([[5], [3]])
    y, _, counts = moe_lib.moe_apply_dense(p, x, cfg, valid=valid)
    want = moe_lib.mlp_apply(p["shared"], x, cfg.act_fn)
    if held:
        e = {k: p[k][expert - 4] for k in ("wi_gate", "wi_up", "wo")}
        want = want + 2.446 * moe_lib.mlp_apply(e, x, cfg.act_fn)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
    assert np.asarray(counts).tolist() == (
        [0, 8, 0, 0] if held else [0, 0, 0, 0])


# prefill and two greedy decode steps of each smoke bundle, read before
# the paged MLA + MoE path came in: sums of |logits| and the last token
GOLDEN = {
    "tinyllama-1.1b": ([30.501827239990234, 32.593719482421875,
                        33.289363861083984], 122, 106816),
    "internvl2-1b": ([33.53296661376953, 32.63134765625,
                      33.981422424316406], 155, 94528),
    "deepseek-v3-671b": ([29.57500648498535, 31.677818298339844,
                          31.92865562438965], 175, 475072),
}


@pytest.mark.parametrize("arch", sorted(GOLDEN))
def test_other_bundles_unchanged(arch):
    """Dense, VLM and DeepSeek-V3 bundles give the parameters and logits
    they gave before; DeepSeek-V3 (MLA caches) now also pages, and its
    paged step equals its dense-cache step."""
    sums, last, n_params = GOLDEN[arch]
    cfg = get_config(arch, smoke=True)
    b = build_model(cfg, compute_dtype=jnp.float32)
    assert b.param_count() == n_params
    assert b.supports_paged_decode
    p = b.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.arange(1, 8, dtype=jnp.int32)[None]}
    n = 7
    if cfg.has_vision_stub:
        batch["image_embeds"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(1), (1, cfg.n_image_tokens, cfg.d_model))
        n += cfg.n_image_tokens
    logits, cache, counts = jax.jit(b.prefill)(
        p, batch, b.init_cache(1, n + 8, jnp.float32))
    # tokens per (MoE layer, held expert); empty without routed experts
    n_moe = cfg.n_layers - cfg.first_dense_layers
    assert counts.shape == ((n_moe, moe_lib.held_experts(cfg))
                            if cfg.family == "moe" else (0, 0))
    got = [float(jnp.sum(jnp.abs(logits)))]
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for i in range(2):
        logits, cache = jax.jit(b.decode_step)(
            p, tok, cache, jnp.asarray([n + i], jnp.int32))
        got.append(float(jnp.sum(jnp.abs(logits))))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    np.testing.assert_allclose(got, sums, rtol=1e-5)
    assert int(tok[0, 0]) == last
    if cfg.family != "moe":
        return
    # the same two steps through the page pool
    pages = b.init_paged_cache(8, 4, jnp.float32)
    from repro.serving.kvcache import insert_pages

    one = b.init_cache(1, 16, jnp.float32)
    logits, one, _ = jax.jit(b.prefill)(p, batch, one)
    pages = insert_pages(pages, one, [1, 2, 3, 4], n)
    tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    paged = []
    for i in range(2):
        logits, pages, counts = jax.jit(b.paged_decode_step)(
            p, tok, pages, tables, jnp.asarray([n + i], jnp.int32))
        paged.append(float(jnp.sum(jnp.abs(logits))))
        assert int(counts.sum()) == cfg.experts_top_k * (
            cfg.n_layers - cfg.first_dense_layers)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    np.testing.assert_allclose(paged, sums[1:], rtol=1e-5)
