"""Model FLOP utilization of the MLA + MoE decode ticks: the operations
the window's decode steps need (``roofline/mla_moe_vlm.py``: routed
pairs only, absorbed attention at the lengths served) over the summed
``decode_tick`` wall time times the chip's bf16 peak, in %."""

import moe_steps
import tick_lengths


def read(ctx):
    ticks = moe_steps.ticks(ctx)
    if not ticks:
        return None
    rf = moe_steps.roofline()
    s = tick_lengths.decoder_sizes(ctx)
    flops = sum(rf.decode_step(s, lens, pairs, touched)[0]
                for _, _, lens, pairs, touched in ticks)
    wall = sum(t1 - t0 for t0, t1, _, _, _ in ticks)
    return 100.0 * flops / (wall * ctx.peaks["bf16_flops_per_s"])
