"""CPU rehearsal sizes for the benchmark's own tests.

The cells run here at smoke widths (a 2-layer decoder of width 64, a
mini CLIP), a few requests a second and a window of a few seconds: the
control flow, the program's path and the reference are the cell's own,
only the sizes are small.  Run with ``python -m pytest bench/tests``.
"""

from __future__ import annotations

import copy
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH.parent / "src", BENCH, BENCH / "metrics"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402

SMOKE_LLM = {"hidden_size": 64, "intermediate_size": 128,
             "num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 2, "vocab_size": 512}
SMOKE_CLIP_VISION = {"hidden_size": 64, "intermediate_size": 256,
                     "num_hidden_layers": 2, "num_attention_heads": 4,
                     "image_size": 32, "patch_size": 8}
SMOKE_CLIP_TEXT = {"hidden_size": 32, "intermediate_size": 128,
                   "num_hidden_layers": 2, "num_attention_heads": 2,
                   "vocab_size": 300, "max_position_embeddings": 12}


SMOKE_LIMITS = {"served_gap": 1e-4, "classify_err": 1e-3,
                "retrieval_err": 1e-3}
# far above any CPU, so every share read on one stays well under 100%
PEAKS = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e14}


def smoke_config(cfg: dict) -> dict:
    """The configuration at CPU sizes: every width cut, same structure."""
    cfg = copy.deepcopy(cfg)
    for part in cfg["parts"]:
        if part["family"] == "vlm":
            part["llm_config"].update(SMOKE_LLM)
            part["n_image_tokens"] = 8
        elif part["family"] == "clip":
            part["vision_config"].update(SMOKE_CLIP_VISION)
            part["text_config"].update(SMOKE_CLIP_TEXT)
            part["projection_dim"] = 16
            part["classes"] = 10
            part["captions_per_image"] = 3
    cfg["serve"].update(max_seq_len=64, decode_pages=8 * 4 + 1,
                        decode_rows=8, max_batch=4)
    cfg["pool_size"] = 4
    cfg["check"] = {"sample_tokens": 40, "sample_answers": 8}
    # float32 on the CPU agrees with the float32 reference to rounding;
    # any token or answer changed lies far above these
    cfg["limits"] = {k: SMOKE_LIMITS[k] for k in cfg["limits"]}
    return cfg


def smoke_traffic(traffic: dict) -> dict:
    """A few requests a second, short answers, a short grace."""
    traffic = copy.deepcopy(traffic)
    for st in traffic["streams"]:
        st["arrivals"]["rate"] = 3.0
        if "burst" in st["arrivals"]:
            st["arrivals"]["burst"].update(rate=6.0, seconds=0.5, every=1.5)
        for t in st["tasks"]:
            if "prompt_tokens" in t:
                t["prompt_tokens"] = {"uniform": [3, 5]}
            if "new_tokens" in t:
                t["new_tokens"]["clip"] = [2, 8]
                t["new_tokens"]["lognormal"]["median"] = 4
    traffic["grace_seconds"] = 60
    traffic["trace"] = {"seconds": 1.0, "before_close_s": 1.5}
    return traffic


UNPROVEN = BENCH / "tests" / "unproven"


def rehearsal_spec(path: Path) -> Path:
    """``BENCHMARK.json`` with the cells that are built and rehearsed but
    not yet proven on the chip (``unproven/cells.json``) added back.
    Their files sit in ``unproven/``, where no cell of the benchmark
    finds them, and hold no rates or limits of their own: the rehearsal
    sets its own."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    extra = json.loads((UNPROVEN / "cells.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        spec[key] += extra[key]
    names = [w["name"] for w in extra["workloads"]]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in extra["also_in"]:
            m["workloads"] = m["workloads"] + names
    path.write_text(json.dumps(spec))
    return path


@pytest.fixture(scope="session")
def smoke_cell(tmp_path_factory):
    import harness

    spec = rehearsal_spec(tmp_path_factory.mktemp("spec") / "BENCHMARK.json")

    def make(name: str):
        cell = harness.Cell.load(name, spec, UNPROVEN if (
            UNPROVEN / f"{name}.json").exists() else harness.BENCH / "traffic")
        cell.config = smoke_config(cell.config)
        cell.traffic = smoke_traffic(cell.traffic)
        return cell

    return make
