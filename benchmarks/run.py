"""Benchmark driver: one section per paper table + roofline + microbench
+ the continuous-batching scheduler.

Prints ``name,us_per_call,derived`` CSV rows (per the harness contract):
simulator latencies are reported in us; `derived` carries the row's full
dict for human inspection.  Alongside the CSV, every section's rows are
snapshotted to ``BENCH_<section>.json`` at the repo root so perf claims
(kernel us/call, simulator latencies, scheduler end-to-end p50/p99) are
diffable against history.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

SNAPSHOT_DIR = Path(__file__).resolve().parents[1]


def _emit(name: str, us, derived):
    d = json.dumps(derived, default=str).replace(",", ";")
    print(f"{name},{us},{d}")


def _snapshot(section: str, rows, error: str | None = None) -> None:
    from benchmarks.diff import machine_profile

    path = SNAPSHOT_DIR / f"BENCH_{section}.json"
    # the machine header lets diff.py refuse cross-machine comparisons:
    # wall-clocks only mean something against a baseline from this box
    payload = {"section": section, "machine": machine_profile(),
               "rows": rows}
    if error is not None:
        payload["error"] = error
    path.write_text(json.dumps(payload, indent=2, default=str) + "\n")


def main() -> int:
    """Run every section (or the one named in argv); returns 1 when any
    section raised, after printing and snapshotting its error."""
    only = sys.argv[1] if len(sys.argv) > 1 else None
    from repro.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        analysis, kernels, microbench, optimality, roofline, serving,
        tables,
    )

    sections = {
        "table_vi": tables.table_vi,
        "table_vii": tables.table_vii,
        "table_ix": tables.table_ix,
        "table_x": tables.table_x,
        "table_xi": tables.table_xi,
        "batching": tables.batching,
        "optimality_89_of_95": lambda: optimality.run(95),
        "roofline": roofline.rows,
        "roofline_summary": roofline.summary,
        "microbench": microbench.run,
        "serving": serving.run,
        "kernels": kernels.run,
        "analysis": analysis.run,
    }
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in sections.items():
        if only and only != name:
            continue
        try:
            rows = fn()
        except Exception as e:  # report, keep the harness going
            err = f"{type(e).__name__}: {e}"
            _emit(name, "", {"error": err})
            _snapshot(name, [], error=err)
            failed += 1
            continue
        for i, row in enumerate(rows):
            us = row.get("us_per_call")
            if us is None:
                for key in ("s2m3_s", "latency_s", "inference_s",
                            "latency_shared_s", "roofline_s", "t_compute_s"):
                    if row.get(key) is not None:
                        us = round(float(row[key]) * 1e6, 1)
                        break
            _emit(f"{name}[{i}]", "" if us is None else us, row)
        _snapshot(name, list(rows))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
