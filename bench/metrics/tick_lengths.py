"""Shared by the decode readers: each window tick with the cache length
of every row it served (prefix + prompt + tokens emitted before it)."""

import importlib.util
from pathlib import Path


def roofline():
    path = Path(__file__).resolve().parents[1] / "roofline" / "vlm.py"
    spec = importlib.util.spec_from_file_location("bench_roofline_vlm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def decoder_sizes(ctx):
    p = ctx.part_of(ctx.built.decoder)
    return p.fam.sizes(p.spec)


def lengths(ctx):
    if ctx.built.decoder is None:
        return []
    n_img = decoder_sizes(ctx)["n_image_tokens"]
    prompt = {q.rid: len(q.prompt) for q in ctx.window.requests
              if q.prompt is not None}
    seen = {}
    out = []
    for t0, t1, rids, _ in ctx.readings.ticks:
        lens = []
        for rid in rids:
            k = seen.get(rid, 0)
            seen[rid] = k + 1
            lens.append(n_img + prompt[rid] + k)
        out.append((t0, t1, lens))
    return out
