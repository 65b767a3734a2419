"""Share of its roofline that the MLA + MoE batch-1 prefill reaches on
the chip: the least time of each traced ``jit_prefill`` (operations of
the image prefix and prompt with routed pairs only and expanded causal
attention, bytes of the weights it needs, ``roofline/mla_moe_vlm.py``)
over its device time, summed over the prefills traced, in %.  The
program computes every held expert on every token, so the share is
low."""

import moe_steps
import tick_lengths


def read(ctx):
    calls = moe_steps.prefills(ctx)
    if not calls:
        return None
    rf = moe_steps.roofline()
    s = tick_lengths.decoder_sizes(ctx)
    return moe_steps.roofline_share(
        ctx, "jit_prefill", calls,
        lambda c: rf.prefill(s, c[2], c[3], c[4]))
