"""Seconds the process spent building programs before due time 0: the
union of the program's compile spans (``repro.obs.compiles``: JAX's
tracing, lowering and backend compiles, loads from the persistent cache
among them) that end before the window opens, in s.  Spans of a
function traced inside another's trace overlap it and count once.  The
program records from the import of ``repro.s2m3`` on, which the set-up
does before its first compile (the seed's key and the weights' draw)."""

import trace_reduce


def read(ctx):
    try:
        from repro.obs import compiles
    except ImportError:
        return None
    rec = compiles.recorder()
    if rec is None:
        return None
    start = ctx.window.start
    spans = [(s.t0, s.t1) for s in rec.tracer.trace.spans
             if s.t1 is not None and s.t1 <= start]
    return sum(b - a for a, b in trace_reduce.union(spans))
