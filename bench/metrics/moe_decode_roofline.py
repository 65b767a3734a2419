"""Share of its roofline that the MLA + MoE paged decode step reaches on
the chip: the least time of each traced ``jit_paged_decode_step`` (the
larger of its operations over the bf16 peak and its bytes over HBM
bandwidth, ``roofline/mla_moe_vlm.py``: routed pairs only, the held
experts the step touched, absorbed attention at the lengths served) over
its device time, summed over the steps traced, in %."""

import moe_steps
import tick_lengths


def read(ctx):
    calls = moe_steps.ticks(ctx)
    if not calls:
        return None
    rf = moe_steps.roofline()
    s = tick_lengths.decoder_sizes(ctx)
    return moe_steps.roofline_share(
        ctx, "jit_paged_decode_step", calls,
        lambda c: rf.decode_step(s, c[2], c[3], c[4]))
