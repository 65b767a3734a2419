"""Model FLOP utilization of the CLIP encoder launches: the operations
the traced ViT and text launches need (``roofline/clip.py``) over the
summed time from each launch's dispatch on the host to the end of its
device run, times the chip's bf16 peak, in %."""

import encode_launches


def read(ctx):
    flops = secs = 0.0
    rf = encode_launches.roofline()
    for role, fn in (("vit", rf.vit), ("text", rf.text)):
        part, launches = encode_launches.launches(ctx, role)
        for t0, _, b, n in launches:
            flops += fn(part.fam.sizes(part.spec), n)[0]
            secs += b - t0
    if not secs:
        return None
    return 100.0 * flops / (secs * ctx.peaks["bf16_flops_per_s"])
