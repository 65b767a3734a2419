"""Each cell's whole run on the CPU at smoke sizes: the program's path,
the open-loop window, the readers and the reference check.  The
four-chip cell runs on four virtual CPU devices."""

import time

import jax
import pytest

import harness
from conftest import PEAKS

CELLS = ["vlm-mt.steady", "s2m3-4chip.mixed"]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_with_every_metric(smoke_cell, name, trace):
    cell = smoke_cell(name)
    devices = jax.devices()[:cell.chips]
    assert len(devices) == cell.chips
    logs = []
    out = harness.run(cell, seed=2**33 + 17, seconds=2.0, trace=bool(trace),
                      t_process=time.perf_counter(), devices=devices,
                      peaks=PEAKS, log=logs.append)
    assert out["correct"], out["checked"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "[bench] compiles inside the window: 0" in logs
    want = cell.per_layer if trace else cell.end_to_end
    assert sorted(out["metrics"]) == sorted(m["name"] for m in want)
    assert set(out["checked"]) == set(cell.config["limits"])
    assert list(out)[-1] == "checked"
    if trace:
        assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
        for k in ("device_ops", "idle_gaps"):
            assert 0 < len(out["breakdown"][k]) <= 10
        for m in cell.per_layer:
            if "roofline" in m["name"] or "mfu" in m["name"]:
                assert 0 < out["metrics"][m["name"]]["value"] <= 105
    if cell.chips == 4:
        chips = [line for line in logs if "modules on chips" in line][0]
        assert len(set(eval(chips.split(": ", 1)[1]).values())) >= 2
