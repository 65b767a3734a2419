"""Share of its roofline that a launch of the shared ViT reaches on the
chip: the least time of the launch (the larger of operations over the
bf16 peak and bytes over HBM bandwidth, ``roofline/clip.py``, for the
images in that launch) over its device time, summed over the launches
traced, in %.  Small batches are bound by reading the weights."""

import encode_launches


def read(ctx):
    part, launches = encode_launches.launches(ctx, "vit")
    if not launches:
        return None
    rf = encode_launches.roofline()
    s = part.fam.sizes(part.spec)
    least = device = 0.0
    for _, a, b, n in launches:
        flops, nbytes = rf.vit(s, n)
        least += max(flops / ctx.peaks["bf16_flops_per_s"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
        device += b - a
    return 100.0 * least / device if device else None
