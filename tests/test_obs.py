"""Observability layer: span/tracer core, per-request trace trees
through the serving stack, the metrics registry, stats_dict()
compatibility, drift reports, and the instrument-lock lint."""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import get_config
from repro.core.cluster import ClusterSpec, DeviceSpec
from repro.core.module import ModelSpec, ModuleSpec
from repro.models.api import build_model
from repro.obs import (
    Counter, Gauge, Histogram, MetricsRegistry, Span, Trace, Tracer,
    slo_summary,
)
from repro.s2m3 import Deployment, Request

GB = 1024**3


# ---- tracer core --------------------------------------------------------

def _fake_clock(start=0.0, step=1.0):
    t = [start]

    def clock():
        t[0] += step
        return t[0]

    return clock


def test_span_iterates_as_legacy_timeline_tuple():
    s = Span("mini-vit", "encode", 1.0, 2.5, rid=7)
    mod, phase, t0, t1 = s
    assert (mod, phase, t0, t1) == ("mini-vit", "encode", 1.0, 2.5)
    assert s.dur == 1.5 and not s.open


def test_tracer_builds_parented_tree_with_injected_clock():
    tr = Tracer(clock=_fake_clock())
    root = tr.begin("request", "request", rid=3, model="vqa")
    child = tr.begin("enc", "encode", rid=3, parent=root)
    tr.end(child)
    tr.end(root)
    trace = tr.trace
    assert trace.validate(3) == []
    tree = trace.tree(3)
    assert tree.name == "request" and tree.attrs["model"] == "vqa"
    kids = trace.children(tree.sid)
    assert [k.phase for k in kids] == ["encode"]
    # injected clock: deterministic timestamps
    assert (tree.t0, kids[0].t0, kids[0].t1, tree.t1) == (1.0, 2.0, 3.0, 4.0)


def test_tracer_end_is_idempotent_and_rejects_bad_sid():
    tr = Tracer(clock=_fake_clock())
    sid = tr.begin("m", "head", rid=0)
    first = tr.end(sid).t1
    assert tr.end(sid).t1 == first          # re-end keeps the first t1
    with pytest.raises(ValueError, match="invalid span id"):
        tr.end(-1)


def test_validate_flags_malformed_trees():
    trace = Trace([
        Span("request", "request", 0.0, 10.0, rid=1, sid=0),
        Span("m", "encode", 2.0, 12.0, rid=1, sid=1, parent=0),
        Span("m", "wait", 1.0, 2.0, rid=1, sid=2, parent=99),
        Span("m", "head", 3.0, None, rid=1, sid=3, parent=0),
    ])
    found = "\n".join(trace.validate(1))
    for needle in ("escapes parent", "orphan", "unclosed"):
        assert needle in found


def test_chrome_trace_export_shape(tmp_path):
    tr = Tracer(clock=_fake_clock())
    root = tr.begin("request", "request", rid=5)
    tr.record("enc", "encode", 2.0, 3.0, rid=5, parent=root, batch=2)
    tr.end(root)
    out = tmp_path / "trace.json"
    tr.trace.save(out)
    data = json.loads(out.read_text())
    events = data["traceEvents"]
    assert len(events) == 2
    for ev in events:
        assert ev["ph"] == "X" and ev["tid"] == 5
    enc = next(e for e in events if e["name"] == "enc:encode")
    assert enc["ts"] == 2e6 and enc["dur"] == 1e6     # seconds -> us
    assert enc["args"]["batch"] == 2 and "parent" in enc["args"]


# ---- metrics registry ---------------------------------------------------

def test_registry_get_or_create_and_kind_collision():
    reg = MetricsRegistry()
    c = reg.counter("x", module="m")
    assert c is reg.counter("x", module="m")          # same labels: same
    assert c is not reg.counter("x", module="n")      # new labels: new
    assert isinstance(reg.gauge("g"), Gauge)
    assert isinstance(reg.histogram("h"), Histogram)
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x", module="m")


def test_counter_rejects_negative_and_histogram_percentiles():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)
    h = reg.histogram("lat")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100 and h.percentile(50) in (50.0, 51.0)
    assert h.percentile(99) == 99.0 and h.max == 100.0
    assert h.percentile(0) == 1.0 and h.percentile(100) == 100.0


def test_registry_thread_safety_under_concurrent_increments():
    reg = MetricsRegistry()
    n_threads, n_iter = 8, 2500

    def work():
        c = reg.counter("hits", worker="shared")
        g = reg.gauge("depth")
        h = reg.histogram("lat")
        for i in range(n_iter):
            c.inc()
            g.track_max(i)
            h.observe(float(i))

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.value("hits", worker="shared") == n_threads * n_iter
    assert reg.histogram("lat").count == n_threads * n_iter
    assert reg.gauge("depth").value == n_iter - 1


def test_metric_lint_fires_on_unlocked_instrument_mutation():
    from repro.analysis.concurrency_lint import lint_source

    bad = """
import threading

class RacyGauge:
    kind = "gauge"

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0

    def set(self, v):
        self._value = v
"""
    diags = lint_source(bad, "<bad>")
    assert any(d.code == "obs/unlocked-metric-mutation" for d in diags)
    # the shipped instruments are clean
    from pathlib import Path

    import repro.obs
    from repro.analysis.concurrency_lint import lint_paths
    from repro.analysis.diagnostics import errors

    assert errors(lint_paths([Path(repro.obs.__file__).parent])) == []


# ---- serving integration: the acceptance fixture ------------------------

@pytest.fixture(scope="module")
def vlm_deployment():
    """Two generative tasks ("caption" + "ocr") sharing a vision encoder
    AND a generative VLM head — every span phase of the serving stack
    (admission/batch/encode/prefill/decode ticks) appears in one trace."""
    cfg = get_config("internvl2-1b", smoke=True)
    bundle = build_model(cfg, compute_dtype=jnp.float32)
    params = bundle.init(jax.random.PRNGKey(0))
    d = cfg.d_model
    enc = ModuleSpec("pix-enc", "encoder", "vision", 4 * d * d,
                     flops_per_query=2e5)
    head = ModuleSpec("vlm-head", "head", "task", 100_000, generative=True,
                      flops_per_query=4e5, kv_bytes_per_token=1024)
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (d, d))
    builders = {
        "pix-enc": lambda: (lambda p, x: jnp.tanh(x @ p), w),
        "vlm-head": lambda: (bundle, params),
    }
    cluster = ClusterSpec(devices=[DeviceSpec(f"dev{i}", GB, 1e9)
                                   for i in range(2)])
    dep = (Deployment(cluster)
           .add_model(ModelSpec("caption", "captioning", (enc,), head),
                      builders)
           .add_model(ModelSpec("ocr", "ocr", (enc,), head))
           .plan("greedy").materialize())
    return dep, cfg


def _vlm_workload(cfg, n=4):
    img = 0.1 * np.random.default_rng(0).standard_normal(
        (cfg.n_image_tokens, cfg.d_model)).astype(np.float32)
    return [Request(rid=i, model=("caption" if i % 2 == 0 else "ocr"),
                    source="dev0", prompt=(1, 2, 3), max_new_tokens=3 + i,
                    inputs={"vision": img}, slo_deadline=30.0)
            for i in range(n)]


_SERVE_KW = dict(decode_rows=2, page_size=8, max_seq_len=64,
                 decode_pages=33)


def test_serve_trace_is_one_contiguous_tree_per_request(vlm_deployment,
                                                        tmp_path):
    """Acceptance: dep.serve() over a two-task shared-encoder workload
    exports Chrome-trace JSON whose spans for one rid form a contiguous
    tree: admission -> batch -> encode -> prefill -> decode ticks."""
    dep, cfg = vlm_deployment
    reqs = _vlm_workload(cfg, n=4)
    results = dep.serve(reqs, **_SERVE_KW)
    trace = dep.trace()
    assert trace.validate() == []                  # every tree contiguous
    assert trace.rids() == [q.rid for q in reqs]

    for q in reqs:
        root = trace.tree(q.rid)                   # exactly one root
        assert root.name == "request"
        assert root.attrs["model"] == q.model
        phases = {s.phase for s in trace.spans_for(q.rid)}
        assert {"request", "admission", "batch", "encode", "prefill",
                "decode", "decode_tick"} <= phases
        # decode ticks nest under the decode residency span
        decode = next(s for s in trace.spans_for(q.rid)
                      if s.phase == "decode")
        ticks = trace.children(decode.sid)
        assert ticks and all(t.phase == "decode_tick" for t in ticks)
        assert all(t.attrs["pages_live"] > 1 for t in ticks)
        assert all(t.attrs["rows"] >= 1 for t in ticks)
    # the shared encoder's spans carry cross-task batch composition
    enc_spans = [s for s in trace.spans
                 if s.name == "pix-enc" and s.phase == "encode"]
    assert any(s.attrs["cross_task"] and
               s.attrs["models"] == ["caption", "ocr"] for s in enc_spans)

    # chrome export: one "X" event per span, one track per rid
    out = tmp_path / "serve_trace.json"
    trace.save(out)
    events = json.loads(out.read_text())["traceEvents"]
    assert len(events) == len(trace)
    assert {e["tid"] for e in events} == {q.rid for q in reqs}

    # results still expose the legacy timeline tuples
    for r in results:
        assert any(stage == "decode" for _, stage, _, _ in r.timeline)


def test_decode_tick_records_four_host_phases(vlm_deployment):
    """Each tick with live rows records tick.form, tick.dispatch,
    tick.sample and tick.commit once; dispatch and sample split the
    tick's decode_tick span exactly, and every phase sits under the
    first live row's root span."""
    dep, cfg = vlm_deployment
    reqs = _vlm_workload(cfg, n=4)
    dep.serve(reqs, **_SERVE_KW)
    trace = dep.trace()
    assert trace.validate() == []
    ticks: dict = {}
    for s in trace.spans:
        if s.phase == "decode_tick":
            ticks.setdefault((s.t0, s.t1), []).append(s)
    phases = {p: sorted((s for s in trace.spans if s.phase == p),
                        key=lambda s: s.t0)
              for p in ("tick.form", "tick.dispatch", "tick.sample",
                        "tick.commit")}
    assert ticks and all(len(v) == len(ticks) for v in phases.values())
    for (t0, t1), rows, form, disp, samp, commit in zip(
            sorted(ticks), (ticks[k] for k in sorted(ticks)),
            *phases.values()):
        assert disp.t0 == t0 and samp.t1 == t1 and disp.t1 == samp.t0
        assert form.t1 <= disp.t0 and commit.t0 >= samp.t1
        first = min(rows, key=lambda s: s.sid)     # rows record in order
        for s in (form, disp, samp, commit):
            assert s.name == "vlm-head"
            assert s.rid == first.rid and s.attrs["rows"] == len(rows)
            assert s.parent == trace.tree(first.rid).sid
    # the residency span still parents only decode ticks
    for q in reqs:
        decode = next(s for s in trace.spans_for(q.rid)
                      if s.phase == "decode")
        assert {k.phase for k in trace.children(decode.sid)} \
            == {"decode_tick"}


def test_compile_recorder_spans_a_fresh_jit_once():
    """A freshly jitted function adds one compile.backend span named
    after it, on the perf_counter clock; a second install registers no
    second listener; compile spans stay out of the serving trace."""
    import time

    from repro.obs import compiles

    rec = compiles.install()
    assert compiles.install() is rec and compiles.recorder() is rec

    def obs_fresh_probe(x):
        return 3.0 * x + 1.0

    n0 = len(rec.tracer.trace)
    before = time.perf_counter()
    jax.jit(obs_fresh_probe)(jnp.arange(5.0)).block_until_ready()
    after = time.perf_counter()
    new = rec.tracer.trace.spans[n0:]
    backend = [s for s in new if s.phase == "compile.backend"
               and "obs_fresh_probe" in s.name]
    assert len(backend) == 1
    assert {s.phase for s in new if "obs_fresh_probe" in s.name} \
        == {"compile.trace", "compile.lower", "compile.backend"}
    # JAX's wall-clock stamps (~1e9 s) carry ~0.25 us of float rounding
    assert before - 1e-6 <= backend[0].t0 <= backend[0].t1 <= after + 1e-6


def test_compile_spans_stay_out_of_the_serving_trace(vlm_deployment):
    from repro.obs import compiles

    dep, cfg = vlm_deployment
    reqs = _vlm_workload(cfg, n=2)
    dep.serve(reqs, **_SERVE_KW)
    assert compiles.recorder() is not None    # importing repro.s2m3 did
    trace = dep.trace()
    assert not [s for s in trace.spans if s.phase.startswith("compile.")]
    tids = {e["tid"] for e in trace.to_chrome_trace()["traceEvents"]}
    assert tids == {q.rid for q in reqs}


def test_importing_the_facade_records_compiles_before_any_engine():
    """A fresh process that imports ``repro.s2m3`` records the compiles
    that come before an engine exists, as a deployment's weight draw."""
    import os
    import subprocess
    import sys

    code = (
        "import repro.s2m3\n"
        "from repro.obs import compiles\n"
        "import jax, jax.numpy as jnp\n"
        "def weight_draw(k):\n"
        "    return jax.random.normal(k, (4, 4), jnp.float32)\n"
        "jax.jit(weight_draw)(jax.random.PRNGKey(0)).block_until_ready()\n"
        "print(sorted({s.phase for s in compiles.recorder().tracer.trace.spans\n"
        "              if 'weight_draw' in s.name}))\n")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               [src] + [p for p in [os.environ.get("PYTHONPATH")] if p])}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split("\n")[-2] == str(
        ["compile.backend", "compile.lower", "compile.trace"])


def test_scheduler_metrics_power_slo_summary(vlm_deployment):
    dep, cfg = vlm_deployment
    reqs = _vlm_workload(cfg, n=4)
    dep.serve(reqs, **_SERVE_KW)
    rows = {r["model"]: r for r in slo_summary(dep.scheduler)}
    assert set(rows) == {"caption", "ocr"}
    for row in rows.values():
        assert row["requests"] == 2
        assert row["p99_ms"] >= row["p50_ms"] > 0
        assert row["slo_requests"] == 2
        assert row["slo_attainment"] == 1.0        # 30 s deadline: trivial


def test_compare_reports_zero_divergence_and_module_ratios(vlm_deployment):
    """Acceptance: dep.compare() on the shared-encoder workload reports
    zero route divergences and a per-module latency ratio table."""
    dep, cfg = vlm_deployment
    reqs = _vlm_workload(cfg, n=4)
    report = dep.compare(reqs, **_SERVE_KW)
    assert report.n_requests == 4
    assert report.routes_checked >= 8              # enc + head per request
    assert report.n_route_divergences == 0
    assert set(report.modules) == {"pix-enc", "vlm-head"}
    for md in report.modules.values():
        assert md.predicted_s > 0 and md.measured_s > 0
        assert md.ratio > 0 and md.n > 0
    assert len(report.request_latency) == 4
    assert report.measured_mean_latency > 0
    assert report.queue_model_error >= 0
    text = report.summary()
    assert "0 divergence(s)" in text and "ratio" in text


def test_stats_dict_zeroed_schema_pre_serve_including_decode(
        vlm_deployment):
    """The registry-backed stats_dict() keeps the stable zeroed schema
    before any serving, for encoder rows AND decode-stream rows."""
    from repro.serving.scheduler import STAT_KEYS, ServeScheduler

    dep, _ = vlm_deployment
    sched = ServeScheduler(dep.engine)
    sd = sched.stats_dict()
    assert set(sd) == set(dep.registry.modules)
    for name, row in sd.items():
        assert set(row) == set(STAT_KEYS)
        assert row["module"] == name
        for key in ("calls", "stages", "max_batch", "cross_task_batches",
                    "max_depth"):
            assert row[key] == 0
        assert row["mean_occupancy"] == 0.0
    # a decode stream created pre-serve reports its keys, all zeroed
    stream = sched._ensure_stream("vlm-head")
    assert stream.decode_steps == 0 and stream.prefills == 0
    row = sched.stats_dict()["vlm-head"]
    for key in ("decode_steps", "decode_tokens", "prefills",
                "cross_task_decode_batches", "live_rows", "waiting"):
        assert row[key] == 0
    assert row["pages_live"] == 1                  # the dummy page
    assert sched.cross_task_batches == 0


def test_rejected_request_root_span_is_closed(vlm_deployment):
    from repro.serving.scheduler import (
        QueueFull, SchedulerConfig, ServeScheduler,
    )

    dep, cfg = vlm_deployment
    sched = ServeScheduler(dep.engine, config=SchedulerConfig(
        max_queue_depth=1, admission="reject", decode_rows=2, page_size=8,
        max_seq_len=64, decode_pages=33))
    reqs = _vlm_workload(cfg, n=4)
    with pytest.raises(QueueFull):
        for q in reqs:
            sched.submit(q)
    sched.drain()
    trace = sched.tracer.trace
    assert trace.validate() == []                  # rejects close cleanly
    rejected = [s for s in trace.spans
                if s.phase == "request" and s.attrs.get("rejected")]
    assert rejected and all(not s.open for s in rejected)


def test_pagepool_registers_occupancy_instruments(vlm_deployment):
    dep, cfg = vlm_deployment
    dep.serve(_vlm_workload(cfg, n=3), **_SERVE_KW)
    mt = dep.scheduler.metrics
    assert mt.value("pagepool.pages_live", module="vlm-head") == 1
    assert mt.value("pagepool.pages_peak", module="vlm-head") > 1
    assert mt.value("pagepool.page_allocs", module="vlm-head") > 0
    assert mt.value("pagepool.seq_frees", module="vlm-head") == 3
    assert dep.scheduler.metrics.total("decode.steps") > 0


def test_obs_self_test_passes():
    from repro.analysis.diagnostics import Severity
    from repro.obs.selftest import self_test

    diags = self_test()
    assert all(d.severity < Severity.ERROR for d in diags)


def test_obs_cli_self_test_exit_code():
    from repro.obs.__main__ import main

    assert main(["--self-test"]) == 0
