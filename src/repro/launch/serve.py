"""Serving launcher: continuous-batching LM server for any --arch.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --smoke --requests 8 --max-new 16

``--plan`` skips serving and instead prints the S2M3 deployment plan for
the arch over the paper's edge testbed (placement, memory ledger,
predicted latency) via the ``s2m3.Deployment`` facade.
"""

from __future__ import annotations

import argparse
import time


def plan_s2m3(cfg, routing: str) -> None:
    """Where would this arch live on the paper's testbed, and how fast
    would a request be?  One facade chain answers both."""
    from repro.core.module import distinct_modules
    from repro.core.profiles import install_profile, make_testbed
    from repro.core.zoo import arch_model_spec, request_for
    from repro.s2m3 import Deployment

    spec = arch_model_spec(cfg)
    cluster = make_testbed(with_server=True)
    install_profile(cluster, distinct_modules([spec]).values())
    dep = (Deployment(cluster)
           .add_model(spec)
           .plan(placement="greedy", routing=routing, replicate=True))
    report = dep.simulate([request_for(spec, 0, "desktop")])
    print(f"[serve] S2M3 plan for {cfg.name}:")
    print(report.summary())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--plan", action="store_true",
                    help="print the S2M3 placement plan and exit")
    ap.add_argument("--routing", default="queue_aware",
                    help="routing policy for --plan (paper | queue_aware)")
    args = ap.parse_args()

    from repro.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp
    import numpy as np

    from repro.common.config import get_config
    from repro.core.routing import Request
    from repro.models.api import build_model
    from repro.serving.scheduler import SchedulerConfig, lm_scheduler

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.plan:
        plan_s2m3(cfg, args.routing)
        return
    bundle = build_model(cfg, compute_dtype=jnp.float32)
    print(f"[serve] {cfg.name} params={bundle.param_count():,}")

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        inputs = {}
        if cfg.has_vision_stub:
            inputs["vision"] = 0.1 * rng.standard_normal(
                (cfg.n_image_tokens, cfg.d_model)).astype(np.float32)
        if cfg.is_encoder_decoder:
            inputs["audio"] = 0.1 * rng.standard_normal(
                (cfg.encoder_seq, cfg.d_model)).astype(np.float32)
        prompt = tuple(rng.integers(1, cfg.vocab_size,
                                    size=rng.integers(2, 8)).tolist())
        reqs.append(Request(rid=i, model="lm", source="dev0", prompt=prompt,
                            max_new_tokens=args.max_new,
                            temperature=args.temperature,
                            inputs=inputs or None))
    t0 = time.time()
    if bundle.supports_paged_decode:
        sched = lm_scheduler(bundle, config=SchedulerConfig(
            decode_rows=args.max_batch, max_seq_len=args.cache_len,
            page_size=16,
            decode_pages=args.max_batch * (-(-args.cache_len // 16)) + 1))
        done = sched.serve(reqs)
        steps = sched.stats_dict()[cfg.name]["decode_steps"]
    else:
        # encoder-decoder families have no paged decode path: fall back
        # to solo prefill+decode per request on a bare engine
        sched = lm_scheduler(bundle)
        done = [sched.engine.generate(q) for q in reqs]
        steps = sum(len(r.output) for r in done)
    dt = time.time() - t0
    total = sum(len(r.output) for r in done)
    for r in done[:4]:
        toks = list(r.output[:12])
        print(f"  req {r.rid}: {toks}{'...' if len(r.output) > 12 else ''}")
    print(f"[serve] {len(done)} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s, {steps} batched decode steps)")


if __name__ == "__main__":
    main()
