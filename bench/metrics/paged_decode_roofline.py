"""Share of its roofline that the batched paged decode step reaches on
the chip: the least time the step needs (the larger of its operations
over the bf16 peak and its bytes over HBM bandwidth, ``roofline/vlm.py``,
from the rows and lengths served) over its device time, summed over the
steps traced, in %.  Each device launch of ``jit_paged_decode_step`` is
matched to the tick whose host interval holds its start.  The step is
bound by bytes: every float32 weight is read once per step."""

import tick_lengths

PROGRAM = "jit_paged_decode_step"


def read(ctx):
    if ctx.trace is None or ctx.built.decoder is None:
        return None
    chip = ctx.built.module_chip[ctx.built.decoder]
    events = ctx.trace.program_events(chip, PROGRAM)
    ticks = tick_lengths.lengths(ctx)
    if not events or not ticks:
        return None
    rf = tick_lengths.roofline()
    s = tick_lengths.decoder_sizes(ctx)
    least = device = 0.0
    i = 0
    for a, b in events:
        while i < len(ticks) and ticks[i][1] < a:
            i += 1
        if i == len(ticks) or not ticks[i][0] <= a <= ticks[i][1]:
            continue
        flops, nbytes = rf.decode_step(s, ticks[i][2])
        least += max(flops / ctx.peaks["bf16_flops_per_s"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
        device += b - a
    return 100.0 * least / device if device else None
