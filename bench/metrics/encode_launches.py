"""Shared by the encoder readers: each encoder launch of the window (the
program's ``encode`` spans, one per request, grouped by launch) matched,
in order, to the device execution of its program on its chip."""

import importlib.util
from pathlib import Path

PROGRAMS = {"vit": "jit_vit_encode", "text": "jit_text_encode"}


def roofline():
    path = Path(__file__).resolve().parents[1] / "roofline" / "clip.py"
    spec = importlib.util.spec_from_file_location("bench_roofline_clip", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def launches(ctx, role):
    """[(dispatch t0, device start, device end, batch rows)] of the
    module holding ``role`` ("vit" or "text"), for launches whose device
    run lies in the trace."""
    parts = [p for p in ctx.built.parts if role in p.roles]
    if ctx.trace is None or not parts:
        return None, []
    part = parts[0]
    module = part.roles[role]
    chip = ctx.built.module_chip[module]
    groups = {}
    for s in ctx.readings.spans:
        if s.phase == "encode" and s.name == module:
            groups.setdefault(s.t0, []).append(s)
    rows = {q.rid: q for q in ctx.window.requests}
    dispatch = []
    for t0, spans in sorted(groups.items()):
        n = sum(int(rows[s.rid].inputs[
            "vision" if role == "vit" else "text"].shape[0]) for s in spans)
        dispatch.append((t0, n))
    events = ctx.trace.program_events(chip, PROGRAMS[role])
    out, j = [], 0
    for a, b in events:
        while j + 1 < len(dispatch) and dispatch[j + 1][0] <= a:
            j += 1
        if j < len(dispatch) and dispatch[j][0] <= a:
            out.append((dispatch[j][0], a, b, dispatch[j][1]))
            j += 1
    return part, out
