"""Serving: paged continuous batching == sequential generation; page
and row reuse; S2M3 engine split/share semantics with real computation."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import get_config
from repro.configs.s2m3_zoo import get_clip_config
from repro.core.module import ModelSpec, ModuleSpec
from repro.core.routing import Request
from repro.models import clip as C
from repro.models.api import build_model
import repro.serving.decode as decode_mod
from repro.serving.decode import DecodeStream
from repro.serving.engine import S2M3Engine
from repro.serving.sampler import select_token
from repro.serving.scheduler import SchedulerConfig, lm_scheduler


def _reference_generate(bundle, params, prompt, n_new, cache_len=64):
    """Sequential greedy decoding oracle (dense contiguous cache)."""
    cache = bundle.init_cache(1, cache_len, dtype=jnp.float32)
    logits, cache, _ = jax.jit(bundle.prefill)(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, cache)
    out = [int(jnp.argmax(logits[0]))]
    length = len(prompt)
    for _ in range(n_new - 1):
        logits, cache = jax.jit(bundle.decode_step)(
            params, jnp.asarray([[out[-1]]], jnp.int32), cache,
            jnp.asarray([length], jnp.int32))
        length += 1
        out.append(int(jnp.argmax(logits[0])))
    return out


@pytest.fixture(scope="module")
def tinyllama():
    cfg = get_config("tinyllama-1.1b", smoke=True)
    bundle = build_model(cfg, compute_dtype=jnp.float32)
    params = bundle.init(jax.random.PRNGKey(0))
    return cfg, bundle, params


def test_continuous_batching_matches_sequential(tinyllama):
    cfg, bundle, params = tinyllama
    sched = lm_scheduler(bundle, params, config=SchedulerConfig(
        decode_rows=3, page_size=8, max_seq_len=64, decode_pages=25))

    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10], [11, 12]]
    reqs = [Request(rid=i, model="lm", source="dev0", prompt=tuple(p),
                    max_new_tokens=6) for i, p in enumerate(prompts)]
    results = sched.serve(reqs)
    assert len(results) == len(prompts)

    for req, res in zip(reqs, results):
        expect = _reference_generate(bundle, params, list(req.prompt), 6)
        assert list(res.output) == expect, (req.rid, list(res.output), expect)


def test_row_and_page_reuse_under_pressure(tinyllama):
    cfg, bundle, params = tinyllama
    # 2 rows, pool sized for barely 2 worst-case sequences: the 5
    # requests must recycle rows AND pages to finish
    sched = lm_scheduler(bundle, params, config=SchedulerConfig(
        decode_rows=2, page_size=8, max_seq_len=32, decode_pages=9))
    reqs = [Request(rid=i, model="lm", source="dev0", prompt=(i + 1,),
                    max_new_tokens=4) for i in range(5)]
    results = sched.serve(reqs)
    assert len(results) == 5
    assert all(len(r.output) == 4 for r in results)
    stream = sched.decode[cfg.name]
    assert stream.rows.n_live == 0
    assert stream.pool.n_seqs == 1            # only the dummy page owner
    assert stream.pool.n_live_pages == 1
    st = sched.stats_dict()[cfg.name]
    assert st["decode_tokens"] == 15          # 5 req * (4 - 1 prefill tok)
    assert st["pages_peak"] >= 3


def test_generative_results_stream_as_they_finish(tinyllama):
    cfg, bundle, params = tinyllama
    order = []
    sched = lm_scheduler(bundle, params,
                         on_finish=lambda r: order.append(r.rid),
                         config=SchedulerConfig(
                             decode_rows=4, page_size=8, max_seq_len=64,
                             decode_pages=33))
    reqs = [Request(rid=i, model="lm", source="dev0", prompt=(1, 2),
                    max_new_tokens=n) for i, n in enumerate((9, 2, 5))]
    sched.serve(reqs)
    # shorter decodes finish (and stream) first, not in admission order
    assert order == [1, 2, 0]


def _recording_stream(tinyllama, rows=4):
    """A fresh decode stream over tinyllama whose engine keeps every
    prefill's logits by prompt and every decode step's logits with the
    rid each row served."""
    cfg, bundle, params = tinyllama
    engine = lm_scheduler(bundle, params).engine
    stream = DecodeStream(engine, cfg.name, rows=rows, n_pages=33,
                          page_size=8, max_seq_len=64)
    prefills, steps = {}, []
    real_prefill = engine.apply_prefill
    real_decode = engine.apply_paged_decode

    def prefill(module, batch, cache):
        logits, cache = real_prefill(module, batch, cache)
        prefills[tuple(np.asarray(batch["tokens"])[0].tolist())] = logits
        return logits, cache

    def decode(module, tokens, cache, tables, lengths):
        logits, cache = real_decode(module, tokens, cache, tables, lengths)
        steps.append(({row: seq.rid for row, seq in stream.live.items()},
                      logits))
        return logits, cache

    engine.apply_prefill = prefill
    engine.apply_paged_decode = decode
    return stream, prefills, steps


def _serve(stream, reqs):
    for q in reqs:
        stream.submit(q.rid, q, {})
    out = {}
    while stream.depth():
        out.update({s.rid: s.tokens for s in stream.tick().finished})
    return out


def _per_row_tokens(reqs, prefills, steps, shift=0):
    """Each request's tokens as the per-row path picks them from the
    recorded logits: greedy rows by argmax, sampling rows with a key
    made from the rid and split once per token."""
    temps = {q.rid: q.temperature for q in reqs}
    keys = {q.rid: jax.random.PRNGKey(q.rid) for q in reqs}

    def pick(rid, logits):
        if temps[rid] <= 0:
            tok = int(jnp.argmax(logits))
        else:
            keys[rid], k = jax.random.split(keys[rid])
            tok = int(select_token(logits, k, temperature=temps[rid]))
        return (tok + shift) % logits.shape[-1]

    out = {q.rid: [pick(q.rid, prefills[q.prompt][0])] for q in reqs}
    for rows, logits in steps:
        for row, rid in sorted(rows.items()):
            out[rid].append(pick(rid, logits[row]))
    return out


def test_mixed_temperature_tick_matches_per_row_path(tinyllama):
    """Greedy rows take the batched pick, sampling rows their own key
    sequence; every token equals what the per-row path picks from the
    same step's logits."""
    stream, prefills, steps = _recording_stream(tinyllama)
    reqs = [Request(rid=i, model="lm", source="dev0", prompt=(i + 1, 2),
                    max_new_tokens=5, temperature=t)
            for i, t in enumerate((0.0, 1.0, 0.0, 0.7))]
    served = _serve(stream, reqs)
    assert any({reqs[rid].temperature > 0 for rid in rows.values()}
               == {False, True} for rows, _ in steps)  # both kinds in a tick
    assert served == _per_row_tokens(reqs, prefills, steps)
    # batched picks count exactly the greedy rows' tick tokens
    assert stream.metrics.total("decode.batched_picks") == 2 * 4
    assert stream.metrics.total("decode.tokens") == 4 * 4


def test_greedy_tick_is_one_pick_call_and_one_host_copy(tinyllama,
                                                        monkeypatch):
    stream, _, _ = _recording_stream(tinyllama)
    reqs = [Request(rid=i, model="lm", source="dev0", prompt=(i + 1,),
                    max_new_tokens=6) for i in range(3)]
    for q in reqs:
        stream.submit(q.rid, q, {})
    stream.tick()                            # admits all three rows
    assert len(stream.live) == 3

    calls = {"pick": 0, "copies": 0, "eager": 0, "split": 0}
    real_pick, real_split = stream._pick, jax.random.split

    def pick(logits):
        calls["pick"] += 1
        return real_pick(logits)

    class CountingNp:
        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, a, *args, **kwargs):
            calls["copies"] += isinstance(a, jax.Array)
            return np.asarray(a, *args, **kwargs)

    def eager(*a, **k):
        calls["eager"] += 1
        return select_token(*a, **k)

    def split(*a, **k):
        calls["split"] += 1
        return real_split(*a, **k)

    monkeypatch.setattr(stream, "_pick", pick)
    monkeypatch.setattr(decode_mod, "np", CountingNp())
    monkeypatch.setattr(decode_mod, "select_token", eager)
    monkeypatch.setattr(jax.random, "split", split)
    report = stream.tick()
    assert report.decode_batch == 3
    assert calls == {"pick": 1, "copies": 1, "eager": 0, "split": 0}
    assert stream.metrics.total("decode.batched_picks") == 2 * 3


def test_patched_select_token_reaches_every_token(tinyllama, monkeypatch):
    """A ``select_token`` patched on ``repro.serving.decode`` before a
    stream is built picks every token the stream serves, on the batched
    greedy path and the per-row sampling path alike."""

    def shifted(logits, *a, **k):
        return (select_token(logits, *a, **k) + 1) % logits.shape[-1]

    monkeypatch.setattr(decode_mod, "select_token", shifted)
    stream, prefills, steps = _recording_stream(tinyllama)
    reqs = [Request(rid=i, model="lm", source="dev0", prompt=(i + 3,),
                    max_new_tokens=6, temperature=t)
            for i, t in enumerate((0.0, 0.0, 0.8))]
    served = _serve(stream, reqs)
    assert all(len(toks) == 6 for toks in served.values())
    assert served == _per_row_tokens(reqs, prefills, steps, shift=1)


def test_vlm_captioning_through_scheduler():
    cfg = get_config("internvl2-1b", smoke=True)
    bundle = build_model(cfg, compute_dtype=jnp.float32)
    params = bundle.init(jax.random.PRNGKey(0))
    sched = lm_scheduler(bundle, params, config=SchedulerConfig(
        decode_rows=2, page_size=8, max_seq_len=64, decode_pages=17))
    img = 0.1 * np.random.default_rng(0).standard_normal(
        (cfg.n_image_tokens, cfg.d_model)).astype(np.float32)
    reqs = [Request(rid=0, model="lm", source="dev0", prompt=(1, 2, 3),
                    max_new_tokens=4, inputs={"vision": img})]
    results = sched.serve(reqs)
    assert len(results) == 1 and len(results[0].output) == 4
    # solo oracle over the same engine: identical tokens
    solo = sched.engine.generate(reqs[0])
    assert list(results[0].output) == list(solo.output)


def test_engine_split_equals_monolithic():
    ccfg = get_clip_config("mini-clip")
    params = C.init_clip(jax.random.PRNGKey(0), ccfg)
    vis = ModuleSpec("mini-vit", "encoder", "vision", 1000)
    txt = ModuleSpec("mini-trf", "encoder", "text", 1000)
    head = ModuleSpec("cosine", "head", "task", 0)
    model = ModelSpec("retrieval", "retrieval", (vis, txt), head)
    engine = S2M3Engine()
    engine.deploy_model(model, {
        "mini-vit": lambda: (partial(C.encode_image, cfg=ccfg), params["vision"]),
        "mini-trf": lambda: (partial(C.encode_text, cfg=ccfg), params["text"]),
        "cosine": lambda: (
            lambda p, enc: C.retrieval_logits(enc["vision"], enc["text"], p),
            params["logit_scale"]),
    })
    patches = jax.random.normal(jax.random.PRNGKey(1),
                                (4, ccfg.n_image_tokens, ccfg.vision_width))
    ids = jax.random.randint(jax.random.PRNGKey(2), (4, 12), 0,
                             ccfg.vocab_size)
    res = engine.infer("retrieval", {"vision": patches, "text": ids})
    mono = C.clip_forward(params, patches, ids, ccfg)
    np.testing.assert_array_equal(np.asarray(res.output), np.asarray(mono))


def test_engine_shares_modules_across_tasks():
    ccfg = get_clip_config("mini-clip")
    params = C.init_clip(jax.random.PRNGKey(0), ccfg)
    vis = ModuleSpec("mini-vit", "encoder", "vision", 1000)
    txt = ModuleSpec("mini-trf", "encoder", "text", 1000)
    builders = {
        "mini-vit": lambda: (partial(C.encode_image, cfg=ccfg), params["vision"]),
        "mini-trf": lambda: (partial(C.encode_text, cfg=ccfg), params["text"]),
        "cosine": lambda: (
            lambda p, enc: C.retrieval_logits(enc["vision"], enc["text"], p),
            params["logit_scale"]),
        "cls": lambda: (lambda p, enc: enc["vision"] @ p,
                        jnp.ones((ccfg.embed_dim, 7))),
    }
    engine = S2M3Engine()
    m1 = ModelSpec("retrieval", "retrieval", (vis, txt),
                   ModuleSpec("cosine", "head", "task", 0))
    m2 = ModelSpec("classify", "classification", (vis,),
                   ModuleSpec("cls", "head", "task", 100))
    loaded1 = engine.deploy_model(m1, builders)
    loaded2 = engine.deploy_model(m2, builders)
    assert "mini-vit" in loaded1 and "mini-vit" not in loaded2
    # eviction keeps shared modules alive while referenced
    freed = engine.evict_model("retrieval")
    assert "mini-vit" not in freed        # still used by classify
    freed = engine.evict_model("classify")
    assert "mini-vit" in freed
