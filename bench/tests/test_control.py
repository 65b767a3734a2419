"""``correct`` comes out false when the timed path is broken, and the
lower-precision control separates from the program.

Each fault test drives a whole run at smoke size (the chip look is the
only part skipped) with one fault planted in the program underneath:
a token altered where it is produced, an answer altered where it is
produced, a decode step that returns its KV cache unchanged, half of
each encoder batch left out, and the exchange of encoder outputs
between chips left out.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import calibrate
import harness
from conftest import PEAKS


def run(cell, seed=2**32 + 3):
    return harness.run(cell, seed=seed, seconds=2.0, trace=False,
                       t_process=time.perf_counter(),
                       devices=jax.devices()[:cell.chips], peaks=PEAKS,
                       log=lambda _: None)


@pytest.mark.parametrize("fault", sorted(calibrate.FAULTS))
def test_generative_faults(smoke_cell, monkeypatch, fault):
    """A token altered where it is produced; a decode step that returns
    its KV cache unchanged."""
    calibrate.FAULTS[fault](monkeypatch.setattr)
    out = run(smoke_cell("vlm-mt.steady"))
    assert out["correct"] is False
    assert out["checked"]["served_gap"]["value"] > 1e-2


@pytest.mark.parametrize("fault", ["answer_altered", "exchange_left_out"])
def test_four_chip_faults(smoke_cell, monkeypatch, fault):
    from repro.serving.engine import S2M3Engine

    real_head = S2M3Engine.apply_head
    real_batch = S2M3Engine.gen_batch

    if fault == "answer_altered":
        def head(self, module, enc, extra=None, *, host=None):
            out, used = real_head(self, module, enc, extra, host=host)
            return out * 1.5 + 0.1, used
        monkeypatch.setattr(S2M3Engine, "apply_head", head)
    else:
        def head(self, module, enc, extra=None, *, host=None):
            zeros = {k: jnp.zeros_like(v) for k, v in enc.items()}
            return real_head(self, module, zeros, extra, host=host)

        def batch(prompt, enc):
            return real_batch(prompt, {k: jnp.zeros_like(v)
                                       for k, v in enc.items()})
        monkeypatch.setattr(S2M3Engine, "apply_head", head)
        monkeypatch.setattr(S2M3Engine, "gen_batch", staticmethod(batch))
    cell = smoke_cell("s2m3-4chip.mixed")
    out = run(cell)
    assert out["correct"] is False
    bad = {k for k, v in out["checked"].items() if v["value"] > v["limit"]}
    assert {"classify_err", "retrieval_err"} <= bad
    if fault == "exchange_left_out":
        assert "served_gap" in bad


def test_half_of_each_encoder_batch_left_out(smoke_cell, monkeypatch):
    """An encoder launch of several rows computes the first half and
    hands those answers to the rest as well."""
    from repro.serving.engine import S2M3Engine

    real = S2M3Engine.apply_module

    def halved(self, module, x, *a, **k):
        n = x.shape[0]
        if n < 2:
            return real(self, module, x, *a, **k)
        out, used = real(self, module, x[: (n + 1) // 2], *a, **k)
        return jnp.resize(out, (n,) + out.shape[1:]), used

    monkeypatch.setattr(S2M3Engine, "apply_module", halved)
    out = run(smoke_cell("s2m3-4chip.mixed"))
    assert out["correct"] is False
    assert out["checked"]["retrieval_err"]["value"] > (
        out["checked"]["retrieval_err"]["limit"])


@pytest.mark.parametrize("name", ["vlm-mt.steady", "s2m3-4chip.mixed"])
def test_lower_precision_control_separates(smoke_cell, name):
    """The fp8 reference in the program's place reads well above what
    the float32 program reads, on three seeds.  The decoder is
    widened here and the window lengthened: at the smallest widths a
    lower-precision copy rarely changes a greedy token."""
    cell = smoke_cell(name)
    for part in cell.config["parts"]:
        if part["family"] == "vlm":
            part["llm_config"].update(hidden_size=128, intermediate_size=256,
                                      vocab_size=8192)
    cell.config["check"]["sample_tokens"] = 200
    for seed in (5, 77, 3):
        built, finished = harness.setup(cell, seed, jax.devices()[:cell.chips],
                                        log=lambda _: None)
        w, _, _ = harness.measure(cell, built, finished, seed=seed,
                                  seconds=6.0, log=lambda _: None)
        sample = harness.sample_served(built, w, seed, cell.config["check"])
        harness.free_program(built)
        ok, detail = harness.compare(built, sample, cell.config["limits"],
                                     control=True)
        assert ok
        got = {k: v for k, (v, _) in detail["compared"].items()}
        ctrl = detail["counts"]
        pairs = [("served_gap", "control_gap"),
                 ("classify_err", "classify_control_err"),
                 ("retrieval_err", "retrieval_control_err")]
        for k, c in pairs:
            if k in got:
                assert ctrl[c] > max(3 * got[k], cell.config["limits"][k]), (
                    seed, k, got[k], ctrl[c])
