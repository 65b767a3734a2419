"""Shared by the MoE readers: each window decode tick with the cache
length of every row it served and the routing the program tagged on it
(``local_pairs``, ``experts_touched``), each window prefill likewise,
and the MLA + MoE roofline.  A program or part without those tags gives
nothing."""

import importlib.util
from pathlib import Path

import tick_lengths


def roofline():
    path = Path(__file__).resolve().parents[1] / "roofline" / "mla_moe_vlm.py"
    spec = importlib.util.spec_from_file_location("bench_roofline_mla_moe",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _routed(span) -> bool:
    return "local_pairs" in span.attrs and "experts_touched" in span.attrs


def ticks(ctx):
    """[(t0, t1, lengths, local_pairs, experts_touched)] per window tick."""
    if ctx.built.decoder is None:
        return []
    tags = {}
    for s in ctx.readings.spans:
        if (s.phase == "decode_tick" and s.name == ctx.built.decoder
                and _routed(s)):
            tags[(s.t0, s.t1)] = (s.attrs["local_pairs"],
                                  s.attrs["experts_touched"])
    out = []
    for t0, t1, lens in tick_lengths.lengths(ctx):
        if (t0, t1) in tags:
            out.append((t0, t1, lens, *tags[(t0, t1)]))
    return out


def prefills(ctx):
    """[(t0, t1, prompt_tokens, local_pairs, experts_touched)] per window
    prefill."""
    if ctx.built.decoder is None:
        return []
    return sorted((s.t0, s.t1, s.attrs["prompt_tokens"],
                   s.attrs["local_pairs"], s.attrs["experts_touched"])
                  for s in ctx.readings.spans
                  if s.phase == "prefill" and s.name == ctx.built.decoder
                  and _routed(s))


def roofline_share(ctx, program: str, calls, cost) -> float | None:
    """Least time over device time, in %, of each traced launch of
    ``program`` on the decoder's chip matched to the call (a tuple whose
    first two items are its host interval) that holds its start;
    ``cost(call)`` gives (operations, bytes)."""
    if ctx.trace is None or not calls:
        return None
    chip = ctx.built.module_chip[ctx.built.decoder]
    events = ctx.trace.program_events(chip, program)
    peaks = ctx.peaks
    least = device = 0.0
    i = 0
    for a, b in events:
        while i < len(calls) and calls[i][1] < a:
            i += 1
        if i == len(calls) or not calls[i][0] <= a <= calls[i][1]:
            continue
        flops, nbytes = cost(calls[i])
        least += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
        device += b - a
    return 100.0 * least / device if device else None
