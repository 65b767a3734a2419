"""The entry point's guards: no result off a TPU, none without the
program beside the benchmark."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import run as bench_run

ROOT = Path(__file__).resolve().parents[2]


def test_main_refuses_a_cpu(capsys):
    code = bench_run.main(["--workload", "vlm-mt.steady", "--seed", "1",
                           "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code == 2
    assert out.out == ""
    assert "'cpu'" in out.err and "TPU" in out.err


def test_unknown_workload_is_refused():
    import pytest

    with pytest.raises(SystemExit):
        bench_run.main(["--workload", "nope", "--seed", "1", "--seconds",
                        "1", "--trace", "0"])


def test_no_result_from_benchmark_files_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, *spec["command"][1:], "--workload",
         spec["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
