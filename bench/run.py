"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  With
``--trace 0`` the last line of stdout holds the cell's end-to-end
metrics; with ``--trace 1`` a profiler trace is taken of a few seconds
inside the window and the line holds the per-layer metrics, the device's
busy and traced seconds and a breakdown.  Every run checks what its
window served against the plain reference; each number compared is
printed beside its limit as the last lines on stderr and under
``checked``, the last key of the line.

A host whose JAX finds no TPU, or fewer chips than the cell needs, exits
with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def setup_paths() -> None:
    for p in (ROOT / "src", BENCH, BENCH / "metrics"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, handed to the program through the variable it reads."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from repro.common.compile_cache import enable_compile_cache

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_compile_cache()


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    setup_paths()
    if not (ROOT / "BENCHMARK.json").exists():
        log("bench: BENCHMARK.json not found beside bench/")
        return 2
    import harness

    cell = harness.Cell.load(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: JAX found platform {devices[0].platform!r}, not "
            "'tpu'; the benchmark runs only on a TPU")
        return 2
    if len(devices) < cell.chips:
        log(f"bench: cell {cell.name} needs {cell.chips} chips, JAX sees "
            f"{len(devices)}")
        return 2
    cache = enable_cache()
    log(f"[bench] {cell.name}: {devices[0].device_kind} x{cell.chips}, "
        f"compile cache {cache}")
    out = harness.run(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_process=T_PROCESS,
                      devices=devices[:cell.chips], log=log)
    emit(out)
    return 0


def emit(out: dict) -> None:
    """Metrics on stdout's last line; each compared number beside its
    limit as the last lines on stderr and last in the line."""
    for k, v in out["metrics"].items():
        log(f"[bench] {k} = {v['value']!r} {v['unit']}")
    checked = out.pop("checked")
    out["checked"] = checked
    for k, v in checked.items():
        log(f"[bench] check {k}: {v['value']!r} (limit {v['limit']!r})")
    log(f"[bench] correct: {out['correct']}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
