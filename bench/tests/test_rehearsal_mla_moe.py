"""The MLA + MoE cell and the overload cell, each run whole on the CPU at
smoke sizes (the program's path, the open-loop window, every reader and
the reference check), and the MLA + MoE part's own pieces: its file
keeps the catalog's numbers, and its weights match the program."""

import copy
import json
import time

import jax
import pytest

import calibrate
import harness
from conftest import PEAKS, SMOKE_LIMITS, smoke_traffic

KIMI = "kimi-mt.docs"
# the part's widths cut, its structure kept: MLA with a direct q, two
# shared experts, one leading dense layer, a share of 4 held experts of
# a 16-wide router that starts past expert 0
SMOKE_MLA_MOE = {"hidden_size": 64, "intermediate_size": 128,
                 "moe_intermediate_size": 32, "num_hidden_layers": 3,
                 "num_attention_heads": 4, "n_routed_experts": 4,
                 "num_experts_per_tok": 3, "kv_lora_rank": 16,
                 "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
                 "v_head_dim": 8, "vocab_size": 512}


def smoke_mla_moe(cfg: dict) -> dict:
    cfg = copy.deepcopy(cfg)
    part = cfg["parts"][0]
    part["llm_config"].update(SMOKE_MLA_MOE)
    part.update(n_image_tokens=8, router_experts=16, first_held_expert=4)
    cfg["serve"].update(max_seq_len=64, decode_pages=8 * 4 + 1,
                        decode_rows=8, max_batch=4)
    cfg["pool_size"] = 4
    cfg["check"] = {"sample_tokens": 40, "sample_answers": 8}
    # float32 on the CPU agrees with the float32 reference to rounding;
    # a changed token, a dropped rope term or a flipped route lies far
    # above
    cfg["limits"] = {k: SMOKE_LIMITS["served_gap"] for k in cfg["limits"]}
    return cfg


@pytest.fixture(scope="module")
def cell_of(smoke_cell):
    def make(name):
        if name != KIMI:
            return smoke_cell(name)
        cell = harness.Cell.load(name)
        cell.config = smoke_mla_moe(cell.config)
        cell.traffic = smoke_traffic(cell.traffic)
        return cell

    return make


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", [KIMI, "vlm-mt.overload"])
def test_new_cell_runs_correct_with_every_metric(cell_of, name, trace):
    cell = cell_of(name)
    devices = jax.devices()[:cell.chips]
    logs = []
    out = harness.run(cell, seed=2**33 + 29, seconds=2.0, trace=bool(trace),
                      t_process=time.perf_counter(), devices=devices,
                      peaks=PEAKS, log=logs.append)
    assert out["correct"], out["checked"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "[bench] compiles inside the window: 0" in logs
    want = cell.per_layer if trace else cell.end_to_end
    assert sorted(out["metrics"]) == sorted(m["name"] for m in want)
    if trace:
        for m in cell.per_layer:
            v = out["metrics"][m["name"]]["value"]
            if m["unit"] == "%":
                assert 0 < v <= 100, (m["name"], v)
        if name == KIMI:
            # each held expert a step touches takes at least one token
            assert out["metrics"]["expert_tokens_per_step"]["value"] >= 1


@pytest.mark.parametrize("fault", sorted(calibrate.FAULTS))
def test_planted_fault_comes_out_incorrect(cell_of, monkeypatch, fault):
    """``kimi-mt.docs``'s own check, through the harness's ``compare``,
    finds a decode step that returns its latent cache unchanged and a
    token altered where it is produced: the mean gap passes its
    limit."""
    calibrate.FAULTS[fault](monkeypatch.setattr)
    cell = cell_of(KIMI)
    out = harness.run(cell, seed=2**33 + 31, seconds=2.0, trace=False,
                      t_process=time.perf_counter(),
                      devices=jax.devices()[:cell.chips], peaks=PEAKS,
                      log=lambda _: None)
    assert out["correct"] is False
    checked = out["checked"]
    assert sorted(checked) == ["large_gap_share", "served_gap_mean"]
    mean = checked["served_gap_mean"]
    assert mean["value"] > 10 * mean["limit"], checked


@pytest.mark.parametrize("held,want", [((1, 3), 0.1), ((3, 4), 0.15),
                                       ((2,), 0.1)])
def test_routing_margin_by_hand(held, want):
    """Top 2 of five scores: the margin is the smallest gap between a
    held expert and the boundary, one chosen held expert falling below
    the best unchosen one, or an unchosen held one rising above the
    weakest chosen one."""
    import jax.numpy as jnp

    from reference import kimi_vl_dec

    v = jnp.asarray([[0.9, 0.8, 0.7, 0.65, 0.6]])
    _, idx = jax.lax.top_k(v, 3)
    is_held = jnp.isin(jnp.arange(5), jnp.asarray(held))
    got = kimi_vl_dec._held_margin(v, idx, 2, is_held)
    assert abs(float(got[0]) - want) < 1e-6


def test_control_reads_far_above_the_program(cell_of):
    """``calibrate.py limits``'s comparison at smoke size: the float32
    program is correct, every number of the check is there, and the
    fp8 control's mean gap lies far above the program's."""
    cell = cell_of(KIMI)
    cell.config["check"]["sample_tokens"] = 120
    seed = 2**33 + 37
    built, finished = harness.setup(cell, seed, jax.devices()[:cell.chips],
                                    log=lambda _: None)
    w, _, _ = harness.measure(cell, built, finished, seed=seed, seconds=4.0,
                              log=lambda _: None)
    sample = harness.sample_served(built, w, seed, cell.config["check"])
    harness.free_program(built)
    ok, detail = harness.compare(built, sample, cell.config["limits"],
                                 control=True)
    assert ok, detail
    got = {k: v for k, (v, _) in detail["compared"].items()}
    n = detail["counts"]
    assert set(n) == {"served_gap", "large_gap_margin_max", "tokens_compared",
                      "control_gap", "control_gap_mean",
                      "control_large_gap_share", "bf16_gap", "bf16_gap_mean",
                      "bf16_large_gap_share"}
    assert got["served_gap_mean"] <= n["served_gap"]
    assert 0.0 <= got["large_gap_share"] <= n["control_large_gap_share"] <= 1
    assert n["control_gap_mean"] > 100 * got["served_gap_mean"]


def test_config_keeps_the_catalog_numbers():
    """The part serves the file's own top-level numbers, and only the
    keys listed in ``reduced`` differ from the published config."""
    cfg = harness.Cell.load(KIMI).config
    llm = cfg["parts"][0]["llm_config"]
    assert {k: cfg[k] for k in llm} == llm
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    pub = cfg["published"]
    assert (llm["num_hidden_layers"], pub["num_hidden_layers"]) == (9, 27)
    assert (llm["n_routed_experts"], pub["n_routed_experts"]) == (16, 64)
    chips = cfg["four_chip_deployment"]["chips"]
    part = cfg["parts"][0]
    assert part["router_experts"] == pub["n_routed_experts"]
    assert llm["n_routed_experts"] * chips == part["router_experts"]


def test_full_size_weights_match_the_program_tree():
    """At published widths the benchmark's weight layout is the
    program's parameter tree, and the head holds 2.119 B parameters."""
    fam = harness.family("mla_moe_vlm")
    part = harness.Cell.load(KIMI).config["parts"][0]
    s = fam.sizes(part)
    from repro.models.api import build_model

    bundle = build_model(fam.arch_config(s))
    assert bundle.supports_paged_decode
    assert bundle.param_count() == fam.n_params(s, head_only=True)
    assert fam.n_params(s) == 2_119_347_200
    assert fam.kv_bytes_per_token(s) == 20_736


def test_traffic_fits_the_served_sizes():
    cfg = harness.Cell.load(KIMI).config
    traffic = json.loads((harness.BENCH / "traffic" /
                          "kimi-mt.docs.json").read_text())
    n_img = cfg["parts"][0]["n_image_tokens"]
    worst = max(t["prompt_tokens"]["uniform"][1] + t["new_tokens"]["clip"][1]
                for t in traffic["streams"][0]["tasks"])
    assert n_img + worst <= cfg["serve"]["max_seq_len"]
    serve = cfg["serve"]
    pages = -(-serve["max_seq_len"] // serve["page_size"])
    assert serve["decode_pages"] == serve["decode_rows"] * pages + 1
