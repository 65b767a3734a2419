"""One run of one benchmark cell, driven by data.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  The harness finds each by its name:

* ``bench/configs/<config>.json``: the deployment.  Its ``parts`` are
  built by ``bench/parts/<family>.py``; its ``serve`` sizes go to the
  program's scheduler; its ``limits`` decide ``correct``.
* ``bench/traffic/<traffic>.json``: the mix, read by ``loadgen``.
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

The run builds the deployment (weights drawn on the device from the
seed), warms every shape the mix uses through ``dep.serve()``, then
submits each request at its due time through ``dep.scheduler.submit()``
and lets the program's own ``step()`` serve them: an open loop, with
no batching, routing or sampling of the harness's own.  After the window
and a grace period it reads the program's spans and counters, frees the
program, and compares a seeded sample of what the window served with
the plain reference.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

import loadgen

BENCH = Path(__file__).resolve().parent
WARM_RID = 10_000_000            # rids of warm-up requests start here


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name: str):
    return load_module(BENCH / "parts" / f"{name}.py")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, name: str, bench_json: Path | None = None,
             traffic_dir: Path = BENCH / "traffic") -> "Cell":
        spec = load_json(bench_json or BENCH.parent / "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        w = cells[name]
        cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
        mine = lambda m: name in m.get("workloads", [name])   # noqa: E731
        return cls(name, w["chips"], load_json(BENCH.parent / cfg["file"]),
                   load_json(traffic_dir / f"{w['traffic']}.json"),
                   [m for m in spec["end_to_end"] if mine(m)],
                   [m for m in spec["per_layer"] if mine(m)])


def jax_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    import jax

    return jax.random.PRNGKey(int(np.random.default_rng(seed)
                                  .integers(0, 2**31 - 1)))


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

@dataclass
class Part:
    spec: dict
    fam: object
    weights: dict
    roles: dict
    pools: dict = field(default_factory=dict)


@dataclass
class Built:
    dep: object
    parts: list[Part]
    serve: dict
    module_chip: dict[str, int]
    decoder: str | None
    encoders: list[str]


def build(config: dict, seed: int, devices: list) -> Built:
    """Plan the configuration's deployment over one placement device per
    chip and materialize it one-to-one onto ``devices``."""
    import jax

    from repro.core.tpu import pod_cluster
    from repro.s2m3 import Deployment

    serve = dict(config["serve"])
    key = jax_key(seed)
    parts, models, builders = [], [], {}
    for i, spec in enumerate(config["parts"]):
        fam = family(spec["family"])
        s = fam.sizes(spec)
        w = fam.make_weights(s, jax.random.fold_in(key, i), devices[0])
        ms, bs, roles = fam.build(spec, w, serve)
        models += ms
        builders.update(bs)
        parts.append(Part(spec, fam, w, roles))
    place = config["placement"]
    n = place["devices"]
    dep = Deployment(pod_cluster([1] * n))
    for m in models:
        dep.add_model(m, builders)
    dep.plan(place["strategy"], routing=place["routing"],
             replicate=place.get("replicate", False))
    names = [d.name for d in dep.cluster.devices]
    dep.materialize(dict(zip(names, devices[:n])))
    eng = dep.engine
    chip_index = {d: i for i, d in enumerate(devices)}
    module_chip = {name: chip_index[rt.device]
                   for name, rt in {**eng.runtimes, **eng.decoders}.items()}
    decoders = [p.roles["decoder"] for p in parts if "decoder" in p.roles]
    encoders = [e for p in parts for e in p.roles.get("encoders", [])]
    for i, p in enumerate(parts):
        p.pools = p.fam.make_pools(p.spec, jax.random.fold_in(key, 100 + i),
                                   devices[0], config["pool_size"])
    return Built(dep, parts, serve, module_chip,
                 decoders[0] if decoders else None, encoders)


def part_for_task(built: Built, task: str) -> Part:
    for p in built.parts:
        if task in p.spec["tasks"]:
            return p
    raise KeyError(f"no part serves task {task!r}")


def requests_for(built: Built, arrivals, seed: int, rid0: int = 0):
    rng = np.random.default_rng([seed, 1])
    src = built.dep.cluster.devices[0].name
    return [part_for_task(built, a.task).fam.make_request(
        part_for_task(built, a.task).spec, a, rid0 + i, src,
        part_for_task(built, a.task).pools, rng)
        for i, a in enumerate(arrivals)]


def warm_arrivals(traffic: dict, serve: dict,
                  encoder_tasks: dict[str, list[str]]
                  ) -> list[list[loadgen.Arrival]]:
    """Groups of requests that reach every shape the mix uses: for each
    encoder, groups of 1 to ``max_batch`` requests of the tasks that use
    it (one launch of each size), and every prompt length of every
    generative task."""
    lengths = loadgen.prompt_lengths(traffic)
    mixed = {t["task"] for st in traffic["streams"] for t in st["tasks"]}
    todo = {t: list(v) for t, v in lengths.items()}

    def arrival(task: str, j: int) -> loadgen.Arrival:
        if task not in lengths:
            return loadgen.Arrival(0.0, "warm", task, payload=j)
        p = todo[task].pop() if todo[task] else lengths[task][0]
        return loadgen.Arrival(0.0, "warm", task, p, 2, j)

    groups = []
    for enc, tasks in sorted(encoder_tasks.items()):
        tasks = [t for t in tasks if t in mixed]
        for k in range(1, serve["max_batch"] + 1 if tasks else 1):
            groups.append([arrival(tasks[j % len(tasks)], j)
                           for j in range(k)])
    while any(todo.values()):
        groups.append([arrival(t, 0) for t in sorted(todo) if todo[t]])
    return groups


def serve_kwargs(serve: dict) -> dict:
    keys = ("max_batch", "max_queue_depth", "decode_rows", "decode_pages",
            "page_size", "max_seq_len")
    return {k: serve[k] for k in keys if k in serve}


def warm(built: Built, traffic: dict, seed: int, on_finish) -> int:
    """Serve the warm-up groups: the first through ``dep.serve()`` (which
    runs the pre-flight and makes ``dep.scheduler``), the rest through
    that scheduler's ``submit()`` and ``drain()``.  Returns the count."""
    encoder_tasks: dict[str, list[str]] = {}
    for m in built.dep.registry.models.values():
        for e in m.encoders:
            encoder_tasks.setdefault(e.name, []).append(m.name)
    groups = warm_arrivals(traffic, built.serve, encoder_tasks)
    rid = WARM_RID
    n = 0
    for gi, group in enumerate(groups):
        reqs = requests_for(built, group, seed, rid)
        rid += len(reqs)
        n += len(reqs)
        if gi == 0:
            built.dep.serve(reqs, on_finish=on_finish,
                            **serve_kwargs(built.serve))
        else:
            for q in reqs:
                built.dep.scheduler.submit(q)
            built.dep.scheduler.drain()
    return n


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts backend compilations (and loads from the persistent cache)
    while ``active``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if self.active and event == self.EVENT:
            self.count += 1


@dataclass
class Window:
    start: float                       # perf_counter of due time 0
    seconds: float
    requests: list
    due: list[float]                   # perf_counter due times
    submitted: list[float]
    finished: dict                     # rid -> (perf_counter, result)
    trace_dir: str | None = None
    trace_span: tuple[float, float] | None = None
    sync: float | None = None          # perf_counter of the sync annotation
    open_at_close: int | None = None   # due requests unfinished at the close


def run_window(built: Built, requests: list, arrivals: list, seconds: float,
               grace: float, finished: dict, *, trace: dict | None = None,
               trace_dir: str | None = None) -> Window:
    """Submit each request at its due time; between submissions let the
    program's scheduler take steps (and check its invariants, as its own
    ``drain()`` does).  Stops once every request is served, or ``grace``
    seconds after the window closes."""
    import jax

    sched = built.dep.scheduler
    start = time.perf_counter() + 0.05
    due = [start + a.due for a in arrivals]
    w = Window(start, seconds, requests, due, [], finished)
    tr_on = tr_off = None
    if trace:
        # near the close: stopping the profiler blocks the host for
        # seconds, and there it delays only the window's last arrivals
        tr_on = start + max(0.0, seconds - trace["before_close_s"])
        tr_off = tr_on + trace["seconds"]
    tracing = False
    i, n = 0, len(requests)
    stop = start + seconds + grace
    while True:
        now = time.perf_counter()
        if tr_on is not None and not tracing and now >= tr_on:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profiler_options())
            with jax.profiler.TraceAnnotation("bench_sync"):
                w.sync = time.perf_counter()
            tracing = True
            w.trace_span = (time.perf_counter(), None)
        if tracing and now >= tr_off:
            t_end = time.perf_counter()
            jax.profiler.stop_trace()
            w.trace_span = (w.trace_span[0], t_end)
            tracing, tr_on = False, None
        while i < n and due[i] <= now:
            sched.submit(requests[i])
            w.submitted.append(time.perf_counter())
            i += 1
        if now >= stop:
            break
        if w.open_at_close is None and now >= start + seconds:
            w.open_at_close = i - len(finished)
        if sched.step():
            if sched.cfg.debug_invariants:
                sched.check_invariants()
            continue
        if i == n and not tracing:
            break
        wake = min(due[i] if i < n else stop,
                   tr_off if tracing else tr_on or stop, stop)
        time.sleep(max(0.0, wake - time.perf_counter()))
    if tracing:
        jax.profiler.stop_trace()
        w.trace_span = (w.trace_span[0], time.perf_counter())
    if w.open_at_close is None:
        w.open_at_close = 0
    w.trace_dir = trace_dir
    return w


# ---------------------------------------------------------------------------
# reading the run
# ---------------------------------------------------------------------------

def pct(values: list[float], q: float) -> float:
    """Nearest-rank percentile; +inf entries (failed requests) count."""
    if not values:
        return math.nan
    xs = sorted(values)
    return float(xs[max(0, math.ceil(q / 100 * len(xs)) - 1)])


@dataclass
class Readings:
    """What the program's own spans and counters say about the window,
    on the harness's clock (seconds from due time 0)."""

    spans: list                        # window spans, times from start
    first_token: dict                  # rid -> time
    token_times: dict                  # rid -> [times]
    finish: dict                       # rid -> time
    due: dict                          # rid -> time
    ticks: list                        # (t0, t1, [rids], pages_live)
    hist0: dict                        # histogram (count, sum) at start


def read(built: Built, w: Window, hist0: dict) -> Readings:
    sched = built.dep.scheduler
    # the scheduler's span clock is perf_counter minus its epoch
    offset = time.perf_counter() - sched.tracer.clock()
    rids = {q.rid for q in w.requests}
    spans = []
    for s in sched.tracer.trace.spans:
        if s.rid in rids and s.t1 is not None:
            s = type(s)(s.name, s.phase, s.t0 + offset - w.start,
                        s.t1 + offset - w.start, s.rid, s.sid, s.parent,
                        dict(s.attrs))
            spans.append(s)
    first, toks, ticks = {}, {}, {}
    for s in spans:
        if s.phase == "prefill":
            first[s.rid] = s.t1
            toks.setdefault(s.rid, []).append(s.t1)
        elif s.phase == "decode_tick":
            toks.setdefault(s.rid, []).append(s.t1)
            key = (s.t0, s.t1)
            if key not in ticks:
                ticks[key] = ([], s.attrs.get("pages_live", 0))
            ticks[key][0].append(s.rid)
    for v in toks.values():
        v.sort()
    return Readings(
        spans, first, toks,
        {rid: t - w.start for rid, (t, _) in w.finished.items()
         if rid in rids},
        {q.rid: d - w.start for q, d in zip(w.requests, w.due)},
        sorted((a, b, r, p) for (a, b), (r, p) in ticks.items()), hist0)


def hist_state(sched) -> dict:
    out = {}
    for inst in sched.metrics.instruments():
        if getattr(inst, "kind", "") == "histogram":
            out[inst.key] = (inst.count, inst.sum)
    return out


def end_to_end(cell: Cell, built: Built, w: Window, r: Readings,
               setup_s: float) -> dict:
    gen = [q for q in w.requests if q.prompt is not None]
    enc = [q for q in w.requests if q.prompt is None]
    inf = float("inf")
    out = {"setup_s": setup_s}
    if gen:
        ttft = [r.first_token[q.rid] - r.due[q.rid]
                if q.rid in r.first_token else inf for q in gen]
        itl = []
        for q in gen:
            ts = r.token_times.get(q.rid, [])
            itl += list(np.diff(ts))
            if q.rid not in r.finish:
                itl += [inf] * max(1, q.max_new_tokens - len(ts))
        in_window = sum(1 for ts in r.token_times.values() for t in ts
                        if 0.0 <= t < w.seconds)
        out.update(ttft_p50_ms=1e3 * pct(ttft, 50),
                   ttft_p95_ms=1e3 * pct(ttft, 95),
                   itl_p50_ms=1e3 * pct(itl, 50),
                   itl_p95_ms=1e3 * pct(itl, 95),
                   output_tokens_per_s=in_window / w.seconds)
    if enc:
        lat = [r.finish[q.rid] - r.due[q.rid] if q.rid in r.finish else inf
               for q in enc]
        out["enc_latency_p95_ms"] = 1e3 * pct(lat, 95)
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample_served(built: Built, w: Window, seed: int, check: dict) -> dict:
    """Per part, a seeded sample of finished window requests with what
    the program returned: for generative parts the longest request and
    others until ``sample_tokens`` served tokens; for encoder parts up to
    ``sample_answers`` answers."""
    rng = np.random.default_rng([seed, 2])
    out = {}
    for i, p in enumerate(built.parts):
        done = [(q, np.asarray(w.finished[q.rid][1].output))
                for q in w.requests
                if q.rid in w.finished and q.model in p.spec["tasks"]]
        if not done:
            out[i] = []
            continue
        order = list(rng.permutation(len(done)))
        if p.fam.KIND == "generative":
            longest = max(range(len(done)), key=lambda j: len(done[j][1]))
            order.remove(longest)
            pick, n = [longest], len(done[longest][1])
            for j in order:
                if n >= check["sample_tokens"]:
                    break
                pick.append(j)
                n += len(done[j][1])
        else:
            pick = order[: check["sample_answers"]]
        out[i] = [done[j] for j in sorted(pick)]
    return out


def compare(built: Built, sample: dict, limits: dict, *,
            control: bool = False) -> tuple[bool, dict]:
    """Run the reference over the sample; every number beside its limit.
    ``correct`` holds when every number is at most its limit."""
    nums = {}
    for i, p in enumerate(built.parts):
        if sample.get(i):
            nums.update(p.fam.check(p.spec, p.weights, sample[i],
                                    serve=built.serve, control=control))
    compared = {k: (v, limits[k]) for k, v in nums.items() if k in limits}
    ok = bool(compared) and all(v <= lim for v, lim in compared.values())
    return ok, {"compared": compared, "counts": {
        k: v for k, v in nums.items() if k not in limits}}


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def device_info(devices, n: int) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": n}


def per_layer(cell: Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


@dataclass
class Context:
    """What a per-layer metric reader may read."""

    cell: Cell
    built: Built
    window: Window
    readings: Readings
    trace: object | None                   # trace_reduce.Summary
    peaks: dict

    def part_of(self, module: str) -> Part:
        for p in self.built.parts:
            roles = p.roles
            if module == roles.get("decoder") or module in roles.get(
                    "encoders", []):
                return p
        raise KeyError(module)


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[kind]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def setup(cell: Cell, seed: int, devices: list, log=print):
    """Build and warm the cell's deployment; returns (built, finished),
    where ``finished`` is the harness's ``on_finish`` record."""
    built = build(cell.config, seed, devices)
    log(f"[bench] modules on chips: {built.module_chip}")
    finished: dict = {}

    def on_finish(result):
        finished[result.rid] = (time.perf_counter(), result)

    n = warm(built, cell.traffic, seed, on_finish)
    log(f"[bench] warm-up requests served: {n}")
    finished.clear()
    return built, finished


def measure(cell: Cell, built: Built, finished: dict, *, seed: int,
            seconds: float, scale: float = 1.0, trace_dir: str | None = None,
            rid0: int = 0, log=print):
    """The open-loop window; returns (window, readings, compiles)."""
    arrivals = loadgen.schedule(cell.traffic, seconds, seed, scale=scale,
                                pool_size=cell.config["pool_size"])
    requests = requests_for(built, arrivals, seed, rid0)
    counter = CompileCounter()
    hist0 = hist_state(built.dep.scheduler)
    counter.active = True
    w = run_window(built, requests, arrivals, seconds,
                   cell.traffic["grace_seconds"], finished,
                   trace=cell.traffic["trace"] if trace_dir else None,
                   trace_dir=trace_dir)
    counter.active = False
    late = [s - d for s, d in zip(w.submitted, w.due)]
    log(f"[bench] window {seconds} s: {len(requests)} requests due, "
        f"{len(finished)} finished; {w.open_at_close} unfinished at the "
        f"close; generator lateness p50 {1e3 * pct(late, 50):.3f} ms, "
        f"p99 {1e3 * pct(late, 99):.3f} ms, max {1e3 * max(late):.3f} ms")
    log(f"[bench] compiles inside the window: {counter.count}")
    return w, read(built, w, hist0), counter.count


def free_program(built: Built) -> None:
    """Drop the program's state (engine, scheduler, caches); the
    benchmark's own weights and payloads stay for the reference."""
    built.dep.scheduler = None
    built.dep.engine = None
    gc.collect()


def profiler_options():
    """Device and annotation events only: tracing every Python call
    would slow the host it measures several times over."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def warm_profiler(path: str) -> None:
    """Start and stop the profiler once, so that its first start (which
    takes seconds on a TPU host) falls in set-up, not in the window."""
    import jax

    jax.profiler.start_trace(path, profiler_options=profiler_options())
    jax.profiler.stop_trace()
    shutil.rmtree(path, ignore_errors=True)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_process: float, devices: list,
        peaks: dict | None = None,
        log=print) -> dict:
    """One whole run; returns the result line's object."""
    import trace_reduce

    built, finished = setup(cell, seed, devices, log)
    tdir = None
    if trace:
        warm_profiler(tempfile.mkdtemp(prefix="bench_trace_"))
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
    w, r, _ = measure(cell, built, finished, seed=seed, seconds=seconds,
                      trace_dir=tdir, log=log)
    setup_s = w.start - t_process
    summary = None
    if trace:
        summary = trace_reduce.reduce_dir(
            w.trace_dir, sync_name="bench_sync", sync_perf=w.sync,
            start=w.trace_span[0], end=w.trace_span[1],
            chips=sorted(set(built.module_chip.values())),
            spans=r.spans, origin=w.start)
        shutil.rmtree(tdir, ignore_errors=True)
    memory = peak_memory(devices[:cell.chips])
    ctx = Context(cell, built, w, r, summary,
                  peaks or peaks_for(devices[0].device_kind))
    if trace:
        metrics = per_layer(cell, ctx)
    else:
        e2e = end_to_end(cell, built, w, r, setup_s)
        log(f"[bench] end-to-end readings: {e2e}")
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end
                   if m["name"] in e2e}
    failed = sum(1 for q in w.requests if q.rid not in finished)
    sample = sample_served(built, w, seed, cell.config["check"])
    free_program(built)
    ok, detail = compare(built, sample, cell.config["limits"])
    out = {"correct": ok, "attempted": len(w.requests), "failed": failed,
           "metrics": metrics,
           "device": {**device_info(devices, cell.chips),
                      "memory_peak_bytes": memory}}
    if summary is not None:
        out["device"]["busy_s"] = summary.busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    for k, v in detail["counts"].items():
        log(f"[bench] reference compared {k}: {v}")
    out["checked"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in detail["compared"].items()}
    return out
