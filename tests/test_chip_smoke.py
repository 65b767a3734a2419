"""chip_smoke.py's phases at smoke sizes on the CPU.

The TPU guard lives only in ``main()``: these tests call the phase
functions with the CPU as the expected platform, and cover the
four-chip comparison by mapping its four placement devices onto the one
CPU device.
"""

import jax
import numpy as np
import pytest

import chip_smoke
from repro.common import compile_cache
from repro.core.routing import Request


@pytest.fixture(scope="module")
def cpu():
    return jax.devices()[0]


def _deployment(n_devices):
    dep, parts = chip_smoke.build_deployment(n_devices, smoke=True)
    return dep, parts, chip_smoke.make_workload(dep, parts)


def test_serve_phase_passes_its_checks(cpu):
    dep, parts, reqs = _deployment(1)
    dep.materialize({dep.cluster.devices[0].name: cpu})
    report = chip_smoke.serve_phase(dep, parts, reqs, platform=cpu.platform)
    assert report["requests"] == len(reqs) == \
        chip_smoke.N_GEN + 2 * chip_smoke.N_CLIP
    assert report["generated_tokens"] == chip_smoke.N_GEN * chip_smoke.MAX_NEW
    assert report["cross_task_decode_batches"] >= 1
    assert {len(q.prompt) for q in reqs if q.prompt} == \
        set(chip_smoke.PROMPT_LENS)


def test_four_chip_phase_on_one_device(cpu):
    dep, parts, reqs = _deployment(4)
    report = chip_smoke.four_chip_phase(dep, parts, reqs, [cpu] * 4,
                                        platform=cpu.platform)
    assert report["chips_holding_modules"] == 1
    assert report["routes_checked"] > 0
    assert set(report["module_chips"]) == {
        "pix-enc", "vlm-head", "mini-vit", "mini-trf", "cosine", "mini-cls"}


def test_module_chips_flags_a_host_outside_the_placement(cpu):
    dep, _, _ = _deployment(4)
    dep.materialize({d.name: cpu for d in dep.cluster.devices})
    assert chip_smoke.module_chips(dep)[0] == []
    dep.engine.runtimes["mini-cls"].host = "elsewhere"
    failures, _ = chip_smoke.module_chips(dep)
    assert len(failures) == 1 and failures[0].startswith("mini-cls")


def test_output_checks_flag_differences(cpu):
    gen = Request(0, "caption", "d", prompt=(1, 2), max_new_tokens=2)
    ret = Request(1, "retrieval", "d")
    toks = np.array([5, 6], np.int32)
    logits = np.full((2, 2), 0.5, np.float32)
    assert chip_smoke._same_outputs([gen, ret], [toks, logits],
                                    [toks, logits + 1e-6], "ref") == []
    failures = chip_smoke._same_outputs(
        [gen, ret], [toks, logits], [toks[::-1], logits + 1e-2], "ref")
    assert [f.split(":")[0] for f in failures] == \
        ["rid 0 (caption)", "rid 1 (retrieval)"]
    with pytest.raises(chip_smoke.SmokeFailure, match="2 check"):
        chip_smoke._require(failures, "serve")

    on_cpu = type("R", (), {"rid": 2, "output": jax.numpy.ones(2),
                            "encoder_outputs": {}})()
    assert chip_smoke._on_platform([on_cpu], cpu.platform) == []
    assert len(chip_smoke._on_platform([on_cpu], "tpu")) == 1


def test_main_refuses_a_host_without_a_tpu(monkeypatch, capsys, tmp_path):
    # with the variable set, the cache helper leaves JAX's config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "'cpu'" in err


def test_compile_cache_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(chip_smoke.ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
