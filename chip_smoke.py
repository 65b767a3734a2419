"""Chip smoke check: serve the S2M3 multi-task path on a TPU at full width.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four-chip placement vs all on chip 0

The deployment is internvl2-1b at its published widths (random weights
from ``--seed``), split the S2M3 way: a vision projection stub shared by
two generative tasks (``caption``, ``ocr``) that also share one VLM
head, beside the encoder-only ``retrieval`` and ``classify`` tasks on
the shared mini-CLIP encoders.  mini-CLIP is the only CLIP width this
repo can run, so its results show the path works and claim nothing
about CLIP at the paper's sizes.

Requests go through ``Deployment.materialize()`` and ``dep.serve()``,
and the run fails (non-zero exit) on any failed check.  Without
``--chips``, the checks are: every request returns; each generative
output equals ``dep.submit()`` of the same request token for token;
the two generative tasks share decode batches; split retrieval agrees
with the monolithic ``clip_forward``; every output array lives on the
chip; and the serve trace is a valid span tree.  ``--chips 4`` runs only
the four-chip path: one plan over four placement devices, materialized
one-to-one onto four chips and again with every placement device on
chip 0, with equal outputs, parameters on the chips their placement
names, modules on at least two chips, and no route divergence between
``simulate()`` and ``serve()``.

The seconds, token counts and memory printed before the last line are
smoke readings, not benchmark numbers.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A host without a TPU exits non-zero before any work.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "internvl2-1b"
N_GEN = 8                    # generative requests, alternating caption/ocr
N_CLIP = 4                   # requests each of retrieval and classify
PROMPT_LENS = (8, 24)        # few lengths: prefill compiles once per length
MAX_NEW = 16
SERVE_KW = dict(decode_rows=4, page_size=16, max_seq_len=320,
                decode_pages=81)
# Split retrieval vs clip_forward: cosine logits lie in [-1, 1] (logit
# scale exp(0) = 1).  The split path batches both tasks' images into one
# encoder launch, so XLA may tile and reassociate float32 sums unlike the
# batch-2 reference: a few ulps per layer.  bf16 arithmetic would be off
# by ~1e-2 and fail.
RTOL = ATOL = 1e-4


class SmokeFailure(RuntimeError):
    """One or more smoke checks failed; the message lists each."""


def _require(failures: list[str], phase: str) -> None:
    if failures:
        raise SmokeFailure(f"{phase}: {len(failures)} check(s) failed:\n  "
                           + "\n  ".join(failures))


@dataclass
class Parts:
    """What the checks need besides the deployment."""

    cfg: object                  # internvl2-1b ArchConfig
    clip_cfg: object
    clip_params: object
    n_params: int                # all modules' parameters


def build_deployment(n_devices: int, *, smoke: bool = False, seed: int = 0):
    """Plan (not materialize) the smoke deployment over ``n_devices``
    one-chip placement devices of 16 GiB each."""
    import jax
    import jax.numpy as jnp

    from benchmarks.serving import clip_models
    from repro.common.config import get_config
    from repro.configs.s2m3_zoo import get_clip_config
    from repro.core.module import ModelSpec, ModuleSpec
    from repro.core.tpu import pod_cluster
    from repro.models import clip as C
    from repro.models.api import build_model
    from repro.s2m3 import Deployment

    cfg = get_config(ARCH, smoke=smoke)
    bundle = build_model(cfg, compute_dtype=jnp.float32)
    k_head, k_enc, k_clip = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = bundle.init(k_head)
    d, n_img = cfg.d_model, cfg.n_image_tokens
    # the config's vision stub: precomputed patch embeddings through one
    # projection; the output reaches the head as image_embeds (EXTRA_KEYS)
    w = jax.random.normal(k_enc, (d, d), jnp.float32) / math.sqrt(d)
    enc = ModuleSpec("pix-enc", "encoder", "vision", d * d,
                     bytes_per_param=4.0, flops_per_query=2.0 * n_img * d * d,
                     input_bytes=4 * n_img * d, output_bytes=4 * n_img * d)
    n_head = bundle.param_count()
    head = ModuleSpec(
        "vlm-head", "head", "task", n_head, bytes_per_param=4.0,
        generative=True,
        flops_per_query=2.0 * n_head * (n_img + max(PROMPT_LENS) + MAX_NEW),
        input_bytes=4 * n_img * d,
        kv_bytes_per_token=2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim
        * 4)
    builders = {"pix-enc": lambda: (lambda p, x: jnp.tanh(x @ p), w),
                "vlm-head": lambda: (bundle, params)}
    ccfg = get_clip_config("mini-clip")
    cparams = C.init_clip(k_clip, ccfg)
    clip_specs, clip_builders = clip_models(ccfg, cparams)

    dep = Deployment(pod_cluster([1] * n_devices))
    dep.add_model(ModelSpec("caption", "captioning", (enc,), head), builders)
    dep.add_model(ModelSpec("ocr", "ocr", (enc,), head))
    for spec in clip_specs:
        if spec.name in ("retrieval", "classify"):
            dep.add_model(spec, clip_builders)
    # Eq. 7 routing: each module runs on its first-fit host, and
    # simulate() predicts that host.  queue_aware spreads the simulator's
    # unbatched requests over replicas while serve() sends each formed
    # batch to one host, so the two would not be comparable route by route.
    dep.plan("greedy", routing="paper", replicate=True)
    n_params = (n_head + d * d
                + sum(x.size for x in jax.tree.leaves(cparams)))
    return dep, Parts(cfg, ccfg, cparams, int(n_params))


def make_workload(dep, parts: Parts, *, seed: int = 0):
    """Greedy generative requests of both tasks, then retrieval and
    classify requests; all inputs drawn from ``seed``."""
    import numpy as np

    from repro.s2m3 import Request

    rng = np.random.default_rng(seed)
    cfg, ccfg = parts.cfg, parts.clip_cfg
    src = dep.cluster.devices[0].name
    reqs = []
    for i in range(N_GEN):
        n = PROMPT_LENS[(i // 2) % len(PROMPT_LENS)]
        prompt = tuple(int(t) for t in rng.integers(1, cfg.vocab_size, n))
        img = rng.standard_normal((cfg.n_image_tokens, cfg.d_model),
                                  dtype=np.float32)
        reqs.append(Request(i, ("caption", "ocr")[i % 2], src,
                            prompt=prompt, max_new_tokens=MAX_NEW,
                            temperature=0.0, inputs={"vision": img}))
    for j in range(N_CLIP):
        patches = rng.standard_normal(
            (2, ccfg.n_image_tokens, ccfg.vision_width), dtype=np.float32)
        ids = rng.integers(0, ccfg.vocab_size, (2, 12)).astype(np.int32)
        reqs.append(Request(N_GEN + 2 * j, "retrieval", src,
                            inputs={"vision": patches, "text": ids}))
        reqs.append(Request(N_GEN + 2 * j + 1, "classify", src,
                            inputs={"vision": patches}))
    return reqs


def _arrays(result) -> list:
    import jax

    leaves = jax.tree.leaves((result.output, result.encoder_outputs))
    return [x for x in leaves if isinstance(x, jax.Array)]


def _returned(reqs, results) -> list[str]:
    got = [None if r is None else r.rid for r in results]
    want = [q.rid for q in reqs]
    return [] if got == want else [f"results for rids {got}, want {want}"]


def _on_platform(results, platform: str) -> list[str]:
    failures = []
    for r in results:
        arrays = _arrays(r)
        kinds = {d.platform for a in arrays for d in a.devices()}
        if not arrays or kinds != {platform}:
            failures.append(f"rid {r.rid}: output arrays on {sorted(kinds)}, "
                            f"want {platform!r}")
    return failures


def _same_outputs(reqs, got, want, what: str) -> list[str]:
    """Token-exact for generative requests, allclose for the others."""
    import numpy as np

    failures = []
    for q, a, b in zip(reqs, got, want):
        x, y = np.asarray(a), np.asarray(b)
        if q.prompt is not None:
            if not np.array_equal(x, y):
                failures.append(f"rid {q.rid} ({q.model}): tokens "
                                f"{x.tolist()} != {what} {y.tolist()}")
        elif x.shape != y.shape:
            failures.append(f"rid {q.rid} ({q.model}): shape {x.shape} != "
                            f"{what} {y.shape}")
        elif not np.allclose(x, y, rtol=RTOL, atol=ATOL):
            failures.append(f"rid {q.rid} ({q.model}): differs from {what} "
                            f"by up to {np.max(np.abs(x - y)):.3g}")
    return failures


def serve_phase(dep, parts: Parts, reqs, *, platform: str = "tpu") -> dict:
    """Serve ``reqs`` twice through ``dep.serve()`` (the first run
    compiles) and check the second against the solo ``dep.submit()``
    reference and ``clip_forward``."""
    from repro.models import clip as C

    t0 = time.perf_counter()
    dep.serve(reqs, **SERVE_KW)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = dep.serve(reqs, **SERVE_KW)
    wall = time.perf_counter() - t0
    failures = _returned(reqs, results)
    failures += [f"trace: {e}" for e in dep.trace().validate()]
    xtask = dep.scheduler.cross_task_decode_batches
    if xtask < 1:
        failures.append("no decode batch mixed caption and ocr rows")
    failures += _on_platform(results, platform)
    gen = [(q, r) for q, r in zip(reqs, results) if q.prompt is not None]
    for q, r in gen:
        if len(r.output) != q.max_new_tokens:
            failures.append(f"rid {q.rid}: {len(r.output)} tokens, want "
                            f"{q.max_new_tokens}")
    failures += _same_outputs([q for q, _ in gen], [r.output for _, r in gen],
                              [dep.submit(q).output for q, _ in gen],
                              "solo submit()")
    ret = [(q, r) for q, r in zip(reqs, results) if q.model == "retrieval"]
    failures += _same_outputs(
        [q for q, _ in ret], [r.output for _, r in ret],
        [C.clip_forward(parts.clip_params, q.inputs["vision"],
                        q.inputs["text"], parts.clip_cfg) for q, _ in ret],
        "clip_forward")
    _require(failures, "serve")
    return {"first_serve_s (compiles)": first, "serve_wall_s": wall,
            "requests": len(results),
            "generated_tokens": sum(len(r.output) for _, r in gen),
            "cross_task_decode_batches": xtask}


def module_chips(dep) -> tuple[list[str], dict[str, str]]:
    """Check each live module's parameters sit on the chip its placement
    host maps to; returns (failures, module -> chip)."""
    import jax

    eng = dep.engine
    failures, chips = [], {}
    live = {**eng.runtimes, **eng.decoders}
    for name, rt in sorted(live.items()):
        hosts = dep.placement.devices_for(name)
        if rt.host not in hosts:
            failures.append(f"{name}: host {rt.host!r} not in placement "
                            f"{hosts}")
            continue
        copies = {rt.host: rt.params, **getattr(rt, "replicas", {})}
        for host, params in copies.items():
            want = eng.device_map[host]
            got = {d for x in jax.tree.leaves(params) for d in x.devices()}
            if got != {want}:
                failures.append(f"{name}@{host}: params on {sorted(map(str, got))}"
                                f", want {want}")
        if rt.device != eng.device_map[rt.host]:
            failures.append(f"{name}: runtime device {rt.device} != "
                            f"{eng.device_map[rt.host]}")
        chips[name] = str(eng.device_map[rt.host])
    return failures, chips


def four_chip_phase(dep, parts: Parts, reqs, devices, *,
                    platform: str = "tpu") -> dict:
    """Materialize one plan one-to-one onto ``devices`` and again with
    every placement device on ``devices[0]``; serve both and compare."""
    names = [d.name for d in dep.cluster.devices]
    dep.materialize(dict(zip(names, devices)))
    split = dep.serve(reqs, **SERVE_KW)
    failures = _returned(reqs, split) + _on_platform(split, platform)
    failures += [f"trace: {e}" for e in dep.trace().validate()]
    placed, chips = module_chips(dep)
    failures += placed
    n_chips = len(set(chips.values()))
    if n_chips < min(2, len(set(devices))):
        failures.append(f"modules on {n_chips} chip(s): {chips}")
    drift = dep.compare(reqs, **SERVE_KW)
    failures += [f"route divergence: rid {d.rid} {d.module} predicted "
                 f"{d.predicted}, ran on {d.actual}"
                 for d in drift.route_divergences]
    dep.materialize({n: devices[0] for n in names})
    single = dep.serve(reqs, **SERVE_KW)
    failures += _returned(reqs, single)
    failures += _same_outputs(reqs, [r.output for r in split],
                              [r.output for r in single], "chip-0 placement")
    _require(failures, "four-chip")
    return {"module_chips": chips, "chips_holding_modules": n_chips,
            "routes_checked": drift.routes_checked}


def _compile_clock():
    """Seconds JAX has spent compiling (or loading compiled programs
    from its cache) since this call."""
    import jax

    total = [0.0]

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    for path in (ROOT / "src", ROOT):
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))
    from repro.common.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found platform {platform!r}, not 'tpu'; "
              "this check runs only on a TPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    devices = devices[:args.chips]
    kind = devices[0].device_kind
    compile_s = _compile_clock()
    print(f"[smoke] device {kind} x{len(devices)}, compile cache {cache_dir}")

    t0 = time.perf_counter()
    dep, parts = build_deployment(args.chips, seed=args.seed)
    reqs = make_workload(dep, parts, seed=args.seed)
    print(f"[smoke] {ARCH} full width + mini-clip: {parts.n_params:,} "
          f"parameters, {len(reqs)} requests")
    if args.chips == 1:
        dep.materialize({dep.cluster.devices[0].name: devices[0]})
        setup = time.perf_counter() - t0
        report = serve_phase(dep, parts, reqs, platform=platform)
    else:
        setup = time.perf_counter() - t0
        report = four_chip_phase(dep, parts, reqs, devices,
                                 platform=platform)
    print(f"[smoke] set-up {setup:.3f} s (build, init, place); "
          f"compile {compile_s():.3f} s over the whole run")
    for k, v in report.items():
        print(f"[smoke] {k}: {v}")
    for i, dev in enumerate(devices):
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"[smoke] chip {i} peak_bytes_in_use: "
              f"{peak if peak is not None else 'not reported'}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
