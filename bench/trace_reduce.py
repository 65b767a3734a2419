"""Reduce a JAX profiler trace to what the per-layer metrics read.

``jax.profiler.ProfileData`` gives planes, their lines and events (start
and duration in ns).  A TPU's plane is ``/device:TPU:<n>``; its ``XLA
Ops`` line holds every operation that ran, its ``XLA Modules`` line one
event per launched program (``jit_<function>(<id>)``).  On a CPU the
operations sit on host threads' lines instead, each with ``hlo_module``
and ``device_ordinal`` stats; those are read the same way, one event per
operation, so the reduction can be tested without a chip.

The two clocks are put together by one harness annotation
(``TraceAnnotation``) whose host ``perf_counter`` time is known.
Everything is then given in seconds from the window's due time 0, the
clock of ``harness.Readings``.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

_ID = re.compile(r"\(\d+\)$")


def program_name(name: str) -> str:
    """``jit_paged_decode_step(1234)`` -> ``jit_paged_decode_step``."""
    return _ID.sub("", name.strip())


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


@dataclass
class Summary:
    start: float                                   # traced window
    end: float
    chips: list[int]
    ops: dict = field(default_factory=dict)        # chip -> [(t0,t1,name,module)]
    programs: dict = field(default_factory=dict)   # chip -> {name: [(t0,t1)]}
    gaps: list = field(default_factory=list)       # (seconds, label, chip)

    @property
    def window_s(self) -> float:
        return self.end - self.start

    def busy(self, chip: int) -> float:
        iv = union(clip([(a, b) for a, b, _, _ in self.ops.get(chip, [])],
                        self.start, self.end))
        return sum(b - a for a, b in iv)

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips the deployment uses."""
        return sum(self.busy(c) for c in self.chips) / len(self.chips)

    def idle_pct(self, chip: int) -> float:
        return 100.0 * (1.0 - self.busy(chip) / self.window_s)

    def program_events(self, chip: int, name: str):
        return sorted(self.programs.get(chip, {}).get(name, []))

    def top_ops(self, n: int = 10) -> list:
        tot: dict = defaultdict(float)
        for chip in self.chips:
            for a, b, op, mod in clip4(self.ops.get(chip, []), self.start,
                                       self.end):
                tot[f"{mod}:{op}" if mod else op] += b - a
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_gaps(self, n: int = 10) -> list:
        """Idle seconds summed by what the host was doing, largest first."""
        tot: dict = defaultdict(float)
        for secs, label, _ in self.gaps:
            tot[label] += secs
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.top_gaps()}


def clip4(ops, lo, hi):
    return [(max(a, lo), min(b, hi), n, m) for a, b, n, m in ops
            if b > lo and a < hi]


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:               # an event without readable stats
        return {}


def find_trace(path: str) -> str:
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return sorted(found)[-1]


def reduce(pd, *, sync_name: str, sync_perf: float, start: float,
           end: float, chips: list[int], spans: list, origin: float,
           min_gap: float = 50e-6) -> Summary:
    """``start``/``end``/``sync_perf``/``origin`` are host perf_counter
    seconds; ``spans`` are program spans already on the origin clock."""
    sync_ns = None
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == sync_name:
                    sync_ns = ev.start_ns
                    break
    if sync_ns is None:
        raise ValueError(f"trace has no {sync_name!r} annotation")

    def t(ns: float) -> float:
        return sync_perf - origin + (ns - sync_ns) * 1e-9

    s = Summary(start - origin, end - origin, list(chips))
    for plane in pd.planes:
        dev = re.match(r"/device:[A-Z]+:(\d+)$", plane.name)
        for line in plane.lines:
            if dev:
                chip = int(dev.group(1))
                if line.name == "XLA Modules":
                    for ev in line.events:
                        s.programs.setdefault(chip, {}).setdefault(
                            program_name(ev.name), []).append(
                            (t(ev.start_ns), t(ev.end_ns)))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        mod = _stats(ev).get("hlo_module", "")
                        s.ops.setdefault(chip, []).append(
                            (t(ev.start_ns), t(ev.end_ns), ev.name,
                             program_name(str(mod))))
            elif plane.name.startswith("/host"):
                for ev in line.events:
                    st = _stats(ev)
                    if "hlo_module" not in st:
                        continue
                    chip = int(st.get("device_ordinal", 0))
                    mod = program_name(str(st["hlo_module"]))
                    iv = (t(ev.start_ns), t(ev.end_ns))
                    s.ops.setdefault(chip, []).append((*iv, ev.name, mod))
                    s.programs.setdefault(chip, {}).setdefault(
                        mod, []).append(iv)
    s.gaps = attribute_gaps(s, spans, min_gap)
    return s


def attribute_gaps(s: Summary, spans: list, min_gap: float) -> list:
    """Each idle interval of each chip, labelled with the innermost
    program span that covers its midpoint (``<module>/<phase>``), or
    ``harness`` where the host was in none."""
    out = []
    timed = [sp for sp in spans if sp.phase not in ("request", "decode")]
    for chip in s.chips:
        busy = union(clip([(a, b) for a, b, _, _ in s.ops.get(chip, [])],
                          s.start, s.end))
        edges = [s.start] + [x for iv in busy for x in iv] + [s.end]
        for a, b in zip(edges[::2], edges[1::2]):
            if b - a < min_gap:
                continue
            mid = 0.5 * (a + b)
            inner = [sp for sp in timed if sp.t0 <= mid <= sp.t1]
            label = "harness"
            if inner:
                sp = min(inner, key=lambda x: x.t1 - x.t0)
                label = f"{sp.name}/{sp.phase}"
            out.append((b - a, label, chip))
    return out


def reduce_dir(path: str, **kw) -> Summary:
    import jax

    return reduce(jax.profiler.ProfileData.from_file(find_trace(path)), **kw)
