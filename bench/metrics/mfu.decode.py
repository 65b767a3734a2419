"""Model FLOP utilization of the decode ticks: the operations that the
window's decode steps need (``roofline/vlm.py``, from the rows and cache
lengths actually served) over the summed ``decode_tick`` wall time times
the chip's bf16 peak, in %.  Float32 matmuls at default precision run as
bf16 passes on the MXU, so the bf16 peak is the denominator."""

import tick_lengths


def read(ctx):
    ticks = tick_lengths.lengths(ctx)
    if not ticks:
        return None
    rf = tick_lengths.roofline()
    s = tick_lengths.decoder_sizes(ctx)
    flops = sum(rf.decode_step(s, lens)[0] for _, _, lens in ticks)
    wall = sum(t1 - t0 for t0, t1, _ in ticks)
    return 100.0 * flops / (wall * ctx.peaks["bf16_flops_per_s"])
