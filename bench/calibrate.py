"""Readings that set a cell's rates and limits, taken on the chip in one
process (so set-up is paid once per seed, not once per reading).

    python3 bench/calibrate.py knee --workload <cell> --scales 0.6,0.8,1.0 \
        --seconds 20 --seed 1
    python3 bench/calibrate.py limits --workload <cell> --seeds 1,2,3 \
        --seconds 15
    python3 bench/calibrate.py fault --fault stale_kv --workload <cell> \
        --seeds 1,2,3 --seconds 15

``knee`` builds and warms the cell once, then runs one open-loop window
per rate scale (every stream's rates times the scale; with ``--steady``
each stream Poisson at its mean rate) and prints what
shows whether the backlog grew: requests unfinished at the window's
close, time to first token in the window's first and second halves, and
tokens per second.

``limits`` runs, for each seed, the cell's own window and sample, and
prints each number the run compares (what the program served against
the reference) beside the same number for the lower-precision control
(the reference with fp8 operands in the program's place).  A limit is set
between the largest sound reading and the smallest control reading.

``fault`` does the same with one fault planted in the program (one of
``FAULTS``), and prints the numbers the run compares.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import run as bench_run


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def steady(traffic: dict, seconds: float) -> dict:
    """The mix with every stream's arrivals made Poisson at its mean
    rate over ``seconds`` (bursts spread out), for a knee sweep."""
    import loadgen

    out = json.loads(json.dumps(traffic))
    for st in out["streams"]:
        st["arrivals"] = {"rate": loadgen.cumulative_rate(
            st["arrivals"], seconds) / seconds}
    return out


def knee(cell, args, devices) -> None:
    import harness

    if args.steady:
        cell.traffic = steady(cell.traffic, args.seconds)
    built, finished = harness.setup(cell, args.seed, devices, log)
    for i, scale in enumerate(float(x) for x in args.scales.split(",")):
        finished.clear()
        w, r, n_compile = harness.measure(
            cell, built, finished, seed=args.seed, seconds=args.seconds,
            scale=scale, rid0=1_000_000 * i, log=log)
        e2e = harness.end_to_end(cell, built, w, r, 0.0)
        half = args.seconds / 2
        gen = [q.rid for q in w.requests if q.prompt is not None]
        ttft = {h: [r.first_token.get(q, float("inf")) - r.due[q]
                    for q in gen if (r.due[q] < half) == (h == 0)]
                for h in (0, 1)}
        enc = [q.rid for q in w.requests if q.prompt is None]
        lat = {h: [r.finish.get(q, float("inf")) - r.due[q]
                   for q in enc if (r.due[q] < half) == (h == 0)]
               for h in (0, 1)}
        row = {"scale": scale, "requests": len(w.requests),
               "rate_per_s": len(w.requests) / args.seconds,
               "unfinished_at_close": w.open_at_close,
               "failed": sum(1 for q in w.requests if q.rid not in finished),
               "compiles": n_compile,
               **{k: v for k, v in e2e.items() if k != "setup_s"}}
        for h in (0, 1):
            if ttft[h]:
                row[f"ttft_p50_ms_half{h}"] = 1e3 * harness.pct(ttft[h], 50)
            if lat[h]:
                row[f"enc_p50_ms_half{h}"] = 1e3 * harness.pct(lat[h], 50)
        print(json.dumps(row), flush=True)
        # let the tail drain before the next rate
        while built.dep.scheduler.step():
            pass


def stale_kv(setattr=setattr) -> None:
    """A decode step that returns its KV cache unchanged."""
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import S2M3Engine

    real = S2M3Engine.apply_paged_decode

    def stale(self, module_name, tokens, cache, block_tables, lengths):
        before = jax.tree.map(jnp.copy, cache)
        logits, _ = real(self, module_name, tokens, cache, block_tables,
                         lengths)
        return logits, before

    setattr(S2M3Engine, "apply_paged_decode", stale)


def token_altered(setattr=setattr) -> None:
    """Each served token moved to the next id where it is produced."""
    import repro.serving.decode as decode

    real = decode.select_token

    def altered(logits, *a, **k):
        return (real(logits, *a, **k) + 1) % logits.shape[-1]

    setattr(decode, "select_token", altered)


FAULTS = {"stale_kv": stale_kv, "token_altered": token_altered}


def limits(cell, args, devices) -> None:
    import harness

    if args.mode == "fault":
        FAULTS[args.fault]()
    for seed in [int(x) for x in args.seeds.split(",")]:
        built, finished = harness.setup(cell, seed, devices, log)
        w, _, _ = harness.measure(cell, built, finished, seed=seed,
                                  seconds=args.seconds, log=log)
        sample = harness.sample_served(built, w, seed, cell.config["check"])
        harness.free_program(built)
        ok, detail = harness.compare(built, sample, cell.config["limits"],
                                     control=args.mode == "limits")
        row = {"seed": seed, "correct": ok,
               **{k: v for k, (v, _) in detail["compared"].items()},
               **detail["counts"]}
        print(json.dumps(row), flush=True)
        del built, finished, w, sample
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("knee", "limits", "fault"))
    ap.add_argument("--fault", choices=sorted(FAULTS), default="stale_kv")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--scales", default="1.0")
    ap.add_argument("--steady", action="store_true",
                    help="knee: Poisson arrivals at each stream's mean rate")
    args = ap.parse_args(argv)
    bench_run.setup_paths()
    import harness

    cell = harness.Cell.load(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"calibrate: needs {cell.chips} TPU chip(s), JAX sees "
            f"{len(devices)} {devices[0].platform!r} device(s)")
        return 2
    bench_run.enable_cache()
    (knee if args.mode == "knee" else limits)(cell, args, devices[:cell.chips])
    return 0


if __name__ == "__main__":
    sys.exit(main())
