"""Scheduler end-to-end benchmark: p50/p99 request latency under a
synthetic multi-task workload (retrieval / classification / VQA sharing
CLIP encoders), plus the queue/batch-occupancy stats that make the
simulator's batching predictions checkable against reality.

Rows feed ``benchmarks/run.py``, which also snapshots them to
``BENCH_serving.json``.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

GB = 1024**3
TASKS = ("retrieval", "classify", "vqa")


def clip_models(ccfg, params):
    """The mini-CLIP tasks (retrieval, classify, vqa) over one shared
    vision and one shared text encoder: their ``ModelSpec``s and the
    module builders for ``Deployment.add_model``."""
    from repro.core.module import ModelSpec, ModuleSpec
    from repro.models import clip as C

    vis = ModuleSpec("mini-vit", "encoder", "vision", 60_000,
                     flops_per_query=2e6)
    txt = ModuleSpec("mini-trf", "encoder", "text", 50_000,
                     flops_per_query=1e6)
    w_lm = jax.random.normal(jax.random.PRNGKey(6),
                             (2 * ccfg.embed_dim, 32)) * 0.3
    builders = {
        "mini-vit": lambda: (partial(C.encode_image, cfg=ccfg),
                             params["vision"]),
        "mini-trf": lambda: (partial(C.encode_text, cfg=ccfg),
                             params["text"]),
        "cosine": lambda: (
            lambda p, enc: C.retrieval_logits(enc["vision"], enc["text"], p),
            params["logit_scale"]),
        "mini-cls": lambda: (lambda p, enc: enc["vision"] @ p,
                             jnp.ones((ccfg.embed_dim, 7))),
        "mini-lm": lambda: (
            lambda p, enc: jnp.concatenate(
                [enc["vision"], enc["text"]], -1) @ p, w_lm),
    }
    models = [
        ModelSpec("retrieval", "retrieval", (vis, txt),
                  ModuleSpec("cosine", "head", "task", 0)),
        ModelSpec("classify", "classification", (vis,),
                  ModuleSpec("mini-cls", "head", "task", 1_000,
                             flops_per_query=1e4)),
        ModelSpec("vqa", "vqa-dec", (vis, txt),
                  ModuleSpec("mini-lm", "head", "task", 80_000,
                             flops_per_query=4e6)),
    ]
    return models, builders


def _deployment():
    from repro.configs.s2m3_zoo import get_clip_config
    from repro.core.cluster import ClusterSpec, DeviceSpec
    from repro.models import clip as C
    from repro.s2m3 import Deployment

    ccfg = get_clip_config("mini-clip")
    params = C.init_clip(jax.random.PRNGKey(0), ccfg)
    models, builders = clip_models(ccfg, params)
    cluster = ClusterSpec(devices=[
        DeviceSpec(f"dev{i}", 1 * GB, (2.0 if i < 2 else 1.0) * 1e9)
        for i in range(4)
    ])
    dep = Deployment(cluster)
    for m in models:
        dep.add_model(m, builders)
    dep.plan("greedy", routing="queue_aware", replicate=True)
    dep.materialize()
    inputs = {
        "vision": jax.random.normal(
            jax.random.PRNGKey(1),
            (2, ccfg.n_image_tokens, ccfg.vision_width)),
        "text": jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0,
                                   ccfg.vocab_size),
    }
    return dep, inputs


def _workload(inputs, n_requests: int):
    from repro.s2m3 import Request

    reqs = []
    for rid in range(n_requests):
        model = TASKS[rid % len(TASKS)]
        inp = dict(inputs)
        if model == "classify":
            inp = {"vision": inp["vision"]}
        reqs.append(Request(rid, model, "dev0", inputs=inp))
    return reqs


def _pct(xs, p):
    return float(np.percentile(np.asarray(xs), p))


def _decode_throughput(n_requests: int = 8, max_new: int = 8):
    """Generative tokens/sec through the paged-KV decode substrate
    (tinyllama smoke head behind ``lm_scheduler``)."""
    from repro.common.config import get_config
    from repro.core.routing import Request
    from repro.models.api import build_model
    from repro.serving.scheduler import SchedulerConfig, lm_scheduler

    cfg = get_config("tinyllama-1.1b", smoke=True)
    bundle = build_model(cfg, compute_dtype=jnp.float32)
    sched = lm_scheduler(bundle, bundle.init(jax.random.PRNGKey(0)),
                         config=SchedulerConfig(
                             decode_rows=4, page_size=8, max_seq_len=64,
                             decode_pages=33))
    reqs = [Request(rid=i, model="lm", source="dev0", prompt=(1 + i, 2, 3),
                    max_new_tokens=max_new) for i in range(n_requests)]
    sched.serve([reqs[0]])          # warm the prefill/decode compiles
    t0 = time.perf_counter()
    done = sched.serve(reqs)
    wall = time.perf_counter() - t0
    st = sched.stats_dict()[cfg.name]
    toks = sum(len(r.output) for r in done)
    return {
        "name": "paged_decode_throughput",
        "n_requests": n_requests,
        "max_new_tokens": max_new,
        "us_per_call": round(wall / max(toks, 1) * 1e6, 1),
        "wall_s": round(wall, 4),
        "decode_tokens_per_s": round(toks / wall, 1),
        "decode_steps": st["decode_steps"],
        "pages_peak": st["pages_peak"],
    }


def run(n_requests: int = 48, max_batch: int = 8):
    dep, inputs = _deployment()
    workload = _workload(inputs, n_requests)

    # warm every compiled path (solo + the batch sizes the run will see)
    for q in workload[:len(TASKS)]:
        dep.submit(q)
    dep.serve(workload, max_batch=max_batch)

    # solo baseline: one-request-at-a-time submit()
    t0 = time.perf_counter()
    solo_lat = [dep.submit(q).latency_s for q in workload]
    solo_wall = time.perf_counter() - t0

    # batched: the continuous-batching scheduler
    t0 = time.perf_counter()
    results = dep.serve(workload, max_batch=max_batch)
    serve_wall = time.perf_counter() - t0
    lat = [r.latency_s for r in results]
    stats = dep.scheduler.stats_dict()

    rows = [{
        "name": "serve_e2e",
        "n_requests": n_requests,
        "max_batch": max_batch,
        "us_per_call": round(serve_wall / n_requests * 1e6, 1),
        "p50_ms": round(_pct(lat, 50) * 1e3, 3),
        "p99_ms": round(_pct(lat, 99) * 1e3, 3),
        "wall_s": round(serve_wall, 4),
        "throughput_rps": round(n_requests / serve_wall, 1),
        "cross_task_batches": dep.scheduler.cross_task_batches,
    }, {
        "name": "solo_submit_baseline",
        "n_requests": n_requests,
        "us_per_call": round(solo_wall / n_requests * 1e6, 1),
        "p50_ms": round(_pct(solo_lat, 50) * 1e3, 3),
        "p99_ms": round(_pct(solo_lat, 99) * 1e3, 3),
        "wall_s": round(solo_wall, 4),
        "throughput_rps": round(n_requests / solo_wall, 1),
    }]
    for mod, st in stats.items():
        rows.append({"name": f"module_{mod}", **st})
    rows.append(_decode_throughput())
    return rows


if __name__ == "__main__":
    for row in run():
        print(row)
