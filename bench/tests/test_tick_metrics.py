"""The decode-tick and compile readers on a hand-built context: spans
and device operations with hand-set times, values worked out by hand."""

from types import SimpleNamespace as NS

import pytest

import harness
import setup_compile_s
import tick_prep_ms
import tick_sample_idle_ms
import trace_reduce as tr

MS = 1e-3


def span(phase, t0, t1, name="vlm-head"):
    return NS(name=name, phase=phase, t0=t0 * MS, t1=t1 * MS,
              dur=(t1 - t0) * MS)


def context(spans, ops=(), start=0.0, end=50.0):
    """Decoder on chip 0; traced 0-50 ms of the window's clock."""
    trace = tr.Summary(start * MS, end * MS, [0])
    trace.ops = {0: [(a * MS, b * MS, "fusion", "jit_paged_decode_step")
                     for a, b in ops]}
    built = NS(decoder="vlm-head", module_chip={"vlm-head": 0})
    return harness.Context(None, built, NS(start=100.0), NS(spans=spans),
                           trace, {})


TICKS = [span("tick.form", 8.0, 8.5), span("tick.dispatch", 9.0, 10.0),
         span("tick.sample", 10.0, 20.0), span("tick.commit", 20.0, 21.0),
         span("tick.form", 28.0, 29.0), span("tick.dispatch", 29.0, 30.5),
         span("tick.sample", 30.5, 40.0), span("tick.commit", 40.0, 40.2),
         # sampled after the trace stopped: read by the host-span metrics
         # only
         span("tick.form", 60.0, 60.5), span("tick.dispatch", 60.5, 61.0),
         span("tick.sample", 61.0, 70.0), span("tick.commit", 70.0, 70.6),
         # another module's spans are not the decoder's
         span("tick.dispatch", 0.0, 50.0, name="other-head")]


def test_tick_sample_idle_by_hand():
    ops = [(5.0, 10.2),                   # enters the first sample
           (11.0, 11.02),                 # 20 us: under the 50 us min_gap
           (12.0, 12.5), (12.4, 12.6),    # overlapping: counted once
           (19.9, 20.3),                  # leaves the first sample
           (45.0, 46.0)]                  # in no sample
    ctx = context(TICKS, ops)
    # first sample 10-20 ms: device busy 0.2 + 0.02 + 0.6 + 0.1 ms
    first = 10.0 - (0.2 + 0.02 + 0.6 + 0.1)
    second = 40.0 - 30.5                  # no operation inside
    assert tick_sample_idle_ms.read(ctx) == pytest.approx(
        (first + second) / 2)


def test_tick_host_phases_by_hand():
    ctx = context(TICKS)
    assert tick_prep_ms.read(ctx) == pytest.approx(
        (0.5 + 1.0 + 1.0 + 1.5 + 0.5 + 0.5) / 3)


def test_tick_readers_read_nothing_without_phase_spans():
    """A program without the phase spans (one tick span alone) gives no
    value, and no error."""
    ctx = context([span("decode_tick", 9.0, 20.0)], [(12.0, 13.0)])
    for reader in (tick_prep_ms, tick_sample_idle_ms):
        assert reader.read(ctx) is None
    untraced = context(TICKS)
    untraced.trace = None
    assert tick_sample_idle_ms.read(untraced) is None


def test_setup_compile_by_hand(monkeypatch):
    from repro.obs import compiles

    rec = compiles.CompileRecorder(clock=lambda: 0.0, wall=lambda: 0.0)
    for name, stage, a, b in [
            ("step", "compile.trace", 91.0, 93.0),
            ("inner", "compile.trace", 92.0, 92.5),     # inside step's trace
            ("jit(step)", "compile.lower", 93.0, 94.0),
            ("jit(step)", "compile.backend", 94.0, 95.0),
            ("jit(late)", "compile.backend", 99.0, 101.0)]:  # ends after 0
        rec.tracer.record(name, stage, a, b)
    monkeypatch.setattr(compiles, "_recorder", rec)
    assert setup_compile_s.read(context(TICKS)) == pytest.approx(4.0)
    monkeypatch.setattr(compiles, "_recorder", None)
    assert setup_compile_s.read(context(TICKS)) is None
