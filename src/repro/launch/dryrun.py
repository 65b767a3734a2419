import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first: jax locks the device count at first
init.  Do not set that flag anywhere global — smoke tests and benches
must see one device.

Single cell:   python -m repro.launch.dryrun --arch tinyllama-1.1b \
                   --shape train_4k [--multi-pod]
Full sweep:    python -m repro.launch.dryrun --all [--jobs 4]
               (spawns one subprocess per cell: isolates XLA state and
                returns memory to the OS between giant compiles)

Artifacts: results/dryrun/<arch>__<shape>__<mesh>.json containing
memory_analysis, cost_analysis, per-op collective bytes (parsed from the
optimized HLO), and the three-term roofline.
"""

import argparse
import json
import pathlib
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[3]
OUT_DIR = REPO / "results" / "dryrun"


# ---------------------------------------------------------------------------
# --perf-variant: each is a named bundle of rule overrides / train-config /
# build options (remat) / arch-config tweaks applied to one cell.
# ---------------------------------------------------------------------------
VARIANTS: dict[str, dict] = {
    "baseline": {},
    # sequence parallelism: shard the query sequence over the model axis —
    # for low-head-count archs whose attention scores cannot head-shard
    "sp": {"rules": {"seq": "model"}},
    # activation-replicated decode for weight-huge models: keep 2D weight
    # sharding, replicate the (tiny) decode activations, move activations
    # not weights (partial matmul + small all-reduce instead of FSDP
    # all-gathering every layer's weights each step)
    "actrep": {"rules": {"batch": None}},
    # replicate attention weights over the model axis at decode so the
    # seq-sharded KV cache is consumed by distributed-softmax partials
    # instead of being all-gathered every layer
    "attnrep": {"rules": {"heads": None, "kv_heads": None}},
    # sp alone fails: wq's head sharding and x's seq sharding fight over
    # the model axis and heads win -> scores replicate.  sp2 releases the
    # (undivisible) head sharding so the sequence keeps the axis.
    "sp2": {"rules": {"seq": "model", "heads": None, "kv_heads": None}},
    # force partial-matmul+all-reduce at decode: shard the activations'
    # hidden dim over data so it MATCHES the weights' contraction-dim
    # sharding (GSPMD only picks partial+AR on matched shardings)
    "actshard": {"rules": {"batch": None, "act_embed": "data"}},
    # sLSTM scan unroll: recurrent weights CSE across unrolled steps
    "slstm8": {"cfg": {"slstm_unroll": 8}},
    "slstm32": {"cfg": {"slstm_unroll": 32}},
    "slstm128": {"cfg": {"slstm_unroll": 128}},
    # + shard the sLSTM recurrent weights over model: R reads and dR
    # all-reduces shrink 16x
    "slstm32shard": {"cfg": {"slstm_unroll": 32},
                     "rules": {"slstm_rec": "model"}},
    # remat policy: save matmul outputs instead of recomputing everything
    "dots": {"opts": {"remat": "dots"}},
    # gradient accumulation: 4 microbatches
    "mb4": {"tcfg": {"microbatches": 4}},
    "mb4dots": {"tcfg": {"microbatches": 4}, "opts": {"remat": "dots"}},
    "spdots": {"rules": {"seq": "model"}, "opts": {"remat": "dots"}},
    "slstm32dots": {"cfg": {"slstm_unroll": 32}, "opts": {"remat": "dots"}},
}


def _sharding_profile(cfg, shape, perf_variant: str):
    """Per-shape-kind logical rule overrides (+ arch-specific, + perf)."""
    kind_rules = {
        "train": {},
        # serving replicates weights over the data axes (no per-layer FSDP
        # gathers) unless the arch is too big to fit (giants override back)
        "prefill": {"embed": None},
        "decode": {"embed": None},
    }[shape.kind]
    rules = dict(kind_rules)
    rules.update(cfg.sharding_overrides.get(shape.kind, {}))
    rules.update(cfg.sharding_overrides.get(shape.name, {}))
    rules.update(VARIANTS.get(perf_variant, {}).get("rules", {}))
    return rules


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             perf_variant: str = "baseline", save_hlo: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.common.config import SHAPES, TrainConfig, get_config
    from repro.common.hw import roofline_terms
    from repro.common.profiling import (
        collective_stats, cost_summary, memory_summary,
    )
    from repro.common.sharding import merge_rules, tree_shardings
    from repro.launch.mesh import make_production_mesh, mesh_tag, require_devices
    from repro.layers.initializers import abstract_tree, spec_param_count
    from repro.models.api import build_model
    from repro.training.optimizer import state_specs
    from repro.training.train_step import make_train_step

    cfg = get_config(arch)
    variant = VARIANTS.get(perf_variant, {})
    if variant.get("cfg"):
        cfg = cfg.with_overrides(**variant["cfg"])
    shape = SHAPES[shape_name]
    tag = mesh_tag(multi_pod)
    n_chips = 512 if multi_pod else 256
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": tag,
        "perf_variant": perf_variant, "n_chips": n_chips,
    }

    if shape_name in cfg.skip_shapes:
        record["skipped"] = cfg.skip_reason
        return record

    require_devices(512)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = merge_rules(_sharding_profile(cfg, shape, perf_variant))

    # Scan-over-layers keeps compile time tractable (the 126-layer x 512-dev
    # giants do not finish when unrolled).  XLA's cost_analysis would count
    # each scan body once, so flops/bytes/collectives come instead from
    # common.hlo_cost, which multiplies while-loop bodies by their
    # known_trip_count through the call graph.
    bundle = build_model(cfg, mesh=mesh, rules=rules,
                         **variant.get("opts", {}))
    n_params = bundle.param_count()
    n_active = bundle.active_param_count()
    record["n_params"] = n_params
    record["n_active_params"] = n_active
    giant = n_params > 100e9

    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            tcfg = TrainConfig(
                moment_dtype="bfloat16" if giant else "float32",
                remat=variant.get("opts", {}).get("remat", "full"),
                **variant.get("tcfg", {}),
            )
            pdt = jnp.bfloat16 if giant else jnp.float32
            sspecs = state_specs(bundle.specs, tcfg)
            state_sds = abstract_tree(
                sspecs, pdt, tree_shardings(sspecs, rules, mesh))
            bspecs = bundle.batch_specs(shape)
            batch_sds = abstract_tree(
                bspecs, jnp.bfloat16, tree_shardings(bspecs, rules, mesh))
            step = make_train_step(bundle, tcfg)
            lowered = jax.jit(step, donate_argnums=(0,)).lower(
                state_sds, batch_sds)
            tokens = shape.global_batch * shape.seq_len
            record["model_flops"] = 6.0 * n_active * tokens
        else:
            pdt = jnp.bfloat16
            params_sds = abstract_tree(
                bundle.specs, pdt, tree_shardings(bundle.specs, rules, mesh))
            bspecs = bundle.batch_specs(shape)
            batch_sds = abstract_tree(
                bspecs, jnp.bfloat16, tree_shardings(bspecs, rules, mesh))
            cspecs = bundle.cache_specs(
                shape.global_batch, shape.seq_len, jnp.bfloat16)
            cache_sds = abstract_tree(
                cspecs, jnp.bfloat16, tree_shardings(cspecs, rules, mesh))
            if shape.kind == "prefill":
                lowered = jax.jit(bundle.prefill).lower(
                    params_sds, batch_sds, cache_sds)
                tokens = shape.global_batch * shape.seq_len
                record["model_flops"] = 2.0 * n_active * tokens
            else:  # decode: one token per sequence
                lowered = jax.jit(bundle.decode_step, donate_argnums=(2,)).lower(
                    params_sds, batch_sds["tokens"], cache_sds,
                    batch_sds["lengths"])
                record["model_flops"] = 2.0 * n_active * shape.global_batch

        record["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)

        mem = memory_summary(compiled)
        cost = cost_summary(compiled)
        print(compiled.memory_analysis())   # proves it fits
        print({k: v for k, v in cost.items() if k != "raw_keys"})

        from repro.common.hlo_cost import analyze as hlo_analyze

        hlo = compiled.as_text()
        rep = hlo_analyze(hlo)              # trip-count-aware per-device costs
        record["memory"] = mem
        record["hbm_per_device_gib"] = round(mem["total_bytes"] / 1024**3, 3)
        record["cost"] = {
            "flops": rep.flops, "bytes": rep.bytes,
            "xla_scan_once_flops": cost["flops"],
            "xla_scan_once_bytes": cost["bytes"],
        }
        record["collectives"] = {
            "bytes_by_op": rep.bytes_by_op,
            "count_by_op": rep.count_by_op,
            "total_bytes": rep.collective_bytes,
        }
        record["roofline"] = roofline_terms(
            rep.flops, rep.bytes, rep.collective_bytes, n_chips,
            per_device=True)
        record["model_vs_hlo_flops"] = (
            record["model_flops"] / (rep.flops * n_chips)
            if rep.flops else None)
        if save_hlo:
            hlo_path = OUT_DIR / f"{arch}__{shape_name}__{tag}.hlo.txt"
            hlo_path.write_text(hlo)
    return record


def _cell_main(args):
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    rec = run_cell(args.arch, args.shape, args.multi_pod,
                   args.perf_variant, args.save_hlo)
    name = f"{args.arch}__{args.shape}__{'multipod2x16x16' if args.multi_pod else 'pod16x16'}"
    if args.perf_variant != "baseline":
        name += f"__{args.perf_variant}"
    out = OUT_DIR / f"{name}.json"
    out.write_text(json.dumps(rec, indent=1))
    status = "SKIP" if "skipped" in rec else "OK"
    print(f"[dryrun] {status} {name} "
          f"(lower {rec.get('lower_s', 0)}s compile {rec.get('compile_s', 0)}s "
          f"hbm/dev {rec.get('hbm_per_device_gib', '-')} GiB)")


def _sweep(jobs: int, multi_pod_only: bool, force: bool):
    from repro.common.config import SHAPES, list_archs

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    cells = []
    for arch in list_archs():
        for shape in SHAPES:
            for mp in ([True] if multi_pod_only else [False, True]):
                tag = "multipod2x16x16" if mp else "pod16x16"
                out = OUT_DIR / f"{arch}__{shape}__{tag}.json"
                if force or not out.exists():
                    cells.append((arch, shape, mp))
    print(f"[dryrun] {len(cells)} cells to run, {jobs} jobs")
    procs: list[tuple[subprocess.Popen, tuple]] = []
    failures = []
    idx = 0
    while idx < len(cells) or procs:
        while idx < len(cells) and len(procs) < jobs:
            arch, shape, mp = cells[idx]
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape]
            if mp:
                cmd.append("--multi-pod")
            log = OUT_DIR / f"log_{arch}__{shape}__{'mp' if mp else 'sp'}.txt"
            p = subprocess.Popen(
                cmd, stdout=log.open("w"), stderr=subprocess.STDOUT,
                env={**os.environ, "PYTHONPATH": str(REPO / "src"),
                     "JAX_PLATFORMS": "cpu"})
            procs.append((p, cells[idx]))
            idx += 1
        done = [(p, c) for p, c in procs if p.poll() is not None]
        procs = [(p, c) for p, c in procs if p.poll() is None]
        for p, c in done:
            if p.returncode != 0:
                failures.append(c)
                print(f"[dryrun] FAIL {c}")
            else:
                print(f"[dryrun] done {c}")
        if procs and not done:
            time.sleep(5)
    if failures:
        print(f"[dryrun] {len(failures)} failures: {failures}")
        sys.exit(1)
    print("[dryrun] sweep complete")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--perf-variant", default="baseline",
                    choices=sorted(VARIANTS))
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    if args.all:
        _sweep(args.jobs, args.multi_pod_only, args.force)
    else:
        assert args.arch and args.shape, "--arch and --shape required"
        _cell_main(args)


if __name__ == "__main__":
    main()
