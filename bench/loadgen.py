"""Open-loop traffic: one general generator for every mix.

A traffic file (``bench/traffic/<mix>.json``) lists streams.  Each stream
has an arrival process and a task mix:

    {"name": "gen",
     "arrivals": {"rate": 10.0,                      # requests/s
                  "burst": {"rate": 20.0, "seconds": 1, "every": 5}},
     "tasks": [{"task": "caption", "share": 0.6,
                "prompt_tokens": {"uniform": [8, 32]},
                "new_tokens": {"lognormal": {"median": 32, "sigma": 0.5},
                               "clip": [8, 64]},
                "payload": "image"}]}

Arrivals follow a Poisson process of the base ``rate``, raised to the
burst's rate for its first ``seconds`` of every ``every`` seconds.

Every seed gets the same work, and at the scale of ``stratum_s`` seconds
the same load.  The window is cut where the rate changes and every
``stratum_s`` seconds; each stretch holds its expected count of
arrivals, with the process's quantile gaps inside it.  The tasks come in
exact shares and each size list holds its distribution's quantiles,
dealt so that every run of about one stratum's requests holds the mix's
shares and a sample from across each size range.  The seed only
shuffles them.  So two seeds differ in order, never in the amount of
work or in how it bunches, and a run's spread is the system's and not
the draw's.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Arrival:
    due: float             # seconds from the window's start
    stream: str
    task: str
    prompt_tokens: int = 0
    new_tokens: int = 0
    payload: int = 0       # index into the part's payload pool


def cumulative_rate(arr: dict, t: float, scale: float = 1.0) -> float:
    """Expected arrivals in [0, t)."""
    b = arr.get("burst")
    if not b:
        return arr["rate"] * scale * t
    period, on = b["every"], b["seconds"]
    full, rest = divmod(t, period)
    per = on * b["rate"] + (period - on) * arr["rate"]
    part = min(rest, on) * b["rate"] + max(rest - on, 0.0) * arr["rate"]
    return (full * per + part) * scale


def segments(arr: dict, seconds: float) -> list[tuple[float, float]]:
    """The window cut where the rate changes, and every ``stratum_s``
    seconds: [(start, end), ...]."""
    edges = {0.0, seconds}
    b = arr.get("burst")
    t = 0.0
    while b and t < seconds:
        edges.update((t, min(t + b["seconds"], seconds)))
        t += b["every"]
    step = arr.get("stratum_s")
    t = 0.0
    while step and t < seconds:
        edges.add(t)
        t += step
    edges = sorted(e for e in edges if e <= seconds)
    return list(zip(edges[:-1], edges[1:]))


def arrival_times(arr: dict, seconds: float, rng: np.random.Generator,
                  scale: float = 1.0) -> np.ndarray:
    """Stratified arrivals.  Each stretch of constant rate gets its
    expected count (rounded on the running total), and inside it the
    exponential-quantile gaps of that many arrivals, shuffled and rescaled
    to the stretch."""
    out = []
    for a, b in segments(arr, seconds):
        n = (int(round(cumulative_rate(arr, b, scale)))
             - int(round(cumulative_rate(arr, a, scale))))
        if n <= 0:
            continue
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        rng.shuffle(gaps)
        unit = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
        out.append(a + (b - a) * unit)
    return np.concatenate(out) if out else np.zeros((0,))


def _quantiles(spec: dict, n: int, rng: np.random.Generator,
               block: int) -> np.ndarray:
    if n == 0:
        return np.zeros((0,), np.int64)
    if "uniform" in spec:
        lo, hi = spec["uniform"]
        vals = lo + np.arange(n) % (hi - lo + 1)
    elif "lognormal" in spec:
        p = spec["lognormal"]
        q = (np.arange(n) + 0.5) / n
        from statistics import NormalDist

        z = np.array([NormalDist().inv_cdf(x) for x in q])
        vals = np.rint(p["median"] * np.exp(p["sigma"] * z))
    elif "fixed" in spec:
        vals = np.full((n,), spec["fixed"])
    else:
        raise ValueError(f"unknown size distribution {spec}")
    if "clip" in spec:
        vals = np.clip(vals, *spec["clip"])
    return spread_order(vals.astype(np.int64), block, rng)


def spread_order(values: np.ndarray, block: int,
                 rng: np.random.Generator) -> np.ndarray:
    """``values`` in an order whose every run of about ``block`` holds a
    sample from across their range: sorted, dealt round-robin into
    blocks, each block shuffled, the blocks shuffled."""
    n = len(values)
    nb = max(1, -(-n // max(1, block)))
    vals = np.sort(values)
    blocks = [vals[j::nb].copy() for j in range(nb)]
    for blk in blocks:
        rng.shuffle(blk)
    order = rng.permutation(nb)
    return (np.concatenate([blocks[j] for j in order]) if n
            else vals)


def _shares(tasks: list, n: int) -> list[int]:
    raw = [t["share"] * n for t in tasks]
    counts = [math.floor(r) for r in raw]
    order = sorted(range(len(tasks)), key=lambda i: counts[i] - raw[i])
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def schedule(traffic: dict, seconds: float, seed: int, *,
             scale: float = 1.0, pool_size: int = 1) -> list[Arrival]:
    """Every arrival due in [0, seconds), in due order."""
    rng = np.random.default_rng(seed)
    out: list[Arrival] = []
    for st in traffic["streams"]:
        arr = st["arrivals"]
        times = arrival_times(arr, seconds, rng, scale)
        n = len(times)
        # requests per stratum: each such run holds the mix's shares
        block = round(n * arr.get("stratum_s", seconds) / seconds)
        labels = spread_order(np.concatenate([
            np.full((c,), i) for i, c in enumerate(_shares(st["tasks"], n))]),
            block, rng)
        sizes = {}
        for i, t in enumerate(st["tasks"]):
            k = int((labels == i).sum())
            kb = round(block * k / n) if n else 1
            sizes[i] = (
                _quantiles(t["prompt_tokens"], k, rng, kb)
                if "prompt_tokens" in t else np.zeros((k,), np.int64),
                _quantiles(t["new_tokens"], k, rng, kb)
                if "new_tokens" in t else np.zeros((k,), np.int64),
                rng.permutation(np.arange(k) % pool_size))
        used = {i: 0 for i in sizes}
        for due, lab in zip(times, labels):
            i = int(lab)
            j = used[i]
            used[i] += 1
            p, m, img = (int(a[j]) for a in sizes[i])
            out.append(Arrival(float(due), st["name"], st["tasks"][i]["task"],
                               p, m, img))
    out.sort(key=lambda a: (a.due, a.stream))
    return out


def prompt_lengths(traffic: dict) -> dict[str, list[int]]:
    """Every prompt length each task can draw (what set-up must warm)."""
    out: dict[str, list[int]] = {}
    for st in traffic["streams"]:
        for t in st["tasks"]:
            spec = t.get("prompt_tokens")
            if spec is None:
                continue
            if "uniform" in spec:
                lo, hi = spec["uniform"]
                out[t["task"]] = list(range(lo, hi + 1))
            elif "fixed" in spec:
                out[t["task"]] = [spec["fixed"]]
            else:
                raise ValueError("warm-up needs a bounded prompt length "
                                 f"list, got {spec}")
    return out

