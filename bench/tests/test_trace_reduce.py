"""The trace reduction: on a trace recorded here on the CPU, and on a
device-shaped trace with hand-set times."""

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import pytest

import trace_reduce as tr


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: list = field(default_factory=list)

    @property
    def end_ns(self):
        return self.start_ns + self.duration_ns


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list


@dataclass
class Prof:
    planes: list


@dataclass
class Span:
    name: str
    phase: str
    t0: float
    t1: float


def device_trace():
    """Host sync at 1,000 ns.  Chip 0 runs two decode launches
    (ops 2-4 us and 6-7 us after the sync), chip 1 one op."""
    host = Plane("/host:CPU", [Line("python", [Ev("bench_sync", 1000, 10)])])
    us = 1000
    dev0 = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_paged_decode_step(77)", 1000 + 2 * us, 2 * us),
                             Ev("jit_paged_decode_step(77)", 1000 + 6 * us, us)]),
        Line("XLA Ops", [Ev("fusion.1", 1000 + 2 * us, 2 * us,
                            [("hlo_module", "jit_paged_decode_step(77)")]),
                         Ev("fusion.2", 1000 + 6 * us, us,
                            [("hlo_module", "jit_paged_decode_step(77)")])])])
    dev1 = Plane("/device:TPU:1", [
        Line("XLA Ops", [Ev("convolution", 1000 + 1 * us, 4 * us,
                            [("hlo_module", "jit_vit_encode(3)")])])])
    return Prof([host, dev0, dev1])


def test_device_trace_by_hand():
    # sync at host perf 100.0 s; window origin 99.0 s; traced 100.0-100.00001
    spans = [Span("vlm-head", "decode_tick", 1.0000039, 1.0000061)]
    s = tr.reduce(device_trace(), sync_name="bench_sync", sync_perf=100.0,
                  start=100.0, end=100.0 + 10e-6, chips=[0, 1], spans=spans,
                  origin=99.0, min_gap=0.5e-6)
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy(0) == pytest.approx(3e-6)
    assert s.busy(1) == pytest.approx(4e-6)
    assert s.busy_s == pytest.approx(3.5e-6)
    assert s.idle_pct(0) == pytest.approx(70.0)
    ev = s.program_events(0, "jit_paged_decode_step")
    assert [round((b - a) * 1e9) for a, b in ev] == [2000, 1000]
    assert ev[0][0] == pytest.approx(1.0 + 2e-6)
    gaps = {(round(g * 1e9), lab) for g, lab, chip in s.gaps if chip == 0}
    # idle 0-2 us (host in no span), 4-6 us (in the decode tick), 7-10 us
    assert gaps == {(2000, "harness"), (2000, "vlm-head/decode_tick"),
                    (3000, "harness")}
    b = s.breakdown()
    assert b["device_ops"][0] == ["jit_vit_encode:convolution",
                                  pytest.approx(4e-6)]
    assert b["idle_gaps"][0][0] == "harness"


def test_recorded_cpu_trace(tmp_path):
    def named_step(x):
        return jnp.tanh(x @ x).sum()

    f = jax.jit(named_step)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    origin = time.perf_counter()
    start = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench_sync"):
        sync = time.perf_counter()
    for _ in range(5):
        f(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    end = time.perf_counter()
    s = tr.reduce_dir(str(tmp_path), sync_name="bench_sync", sync_perf=sync,
                      start=start, end=end, chips=[0], spans=[],
                      origin=origin)
    ev = s.program_events(0, "jit_named_step")
    assert ev, sorted(s.programs.get(0, {}))
    assert all(start - origin - 1e-3 <= a <= b <= end - origin + 1e-3
               for a, b in ev)
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_pct(0) < 100
    assert s.top_gaps()[0][0] == "harness"
