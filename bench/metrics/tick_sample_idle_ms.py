"""Mean time per traced decode tick that the decoder's chip sits idle
while the host samples: the length of each ``tick.sample`` span (per-row
logits slice, key split, token choice, host sync) less the union of the
chip's device operations inside it, averaged over the ticks whose
sampling lies in the traced seconds, in ms.  Every gap counts, also
those shorter than the breakdown's least labelled gap."""

from bisect import bisect_right

import trace_reduce


def read(ctx):
    if ctx.trace is None or ctx.built.decoder is None:
        return None
    lo, hi = ctx.trace.start, ctx.trace.end
    samples = [(s.t0, s.t1) for s in ctx.readings.spans
               if s.name == ctx.built.decoder and s.phase == "tick.sample"
               and lo <= s.t0 and s.t1 <= hi]
    if not samples:
        return None
    chip = ctx.built.module_chip[ctx.built.decoder]
    busy = trace_reduce.union([(a, b) for a, b, _, _
                               in ctx.trace.ops.get(chip, [])])
    ends = [b for _, b in busy]
    idle = 0.0
    for a, b in samples:
        covered = 0.0
        for x, y in busy[bisect_right(ends, a):]:
            if x >= b:
                break
            covered += min(y, b) - max(x, a)
        idle += (b - a) - covered
    return 1e3 * idle / len(samples)
