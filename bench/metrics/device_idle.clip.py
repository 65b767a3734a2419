"""Idle share of the chip that holds the shared CLIP vision tower, over
the traced part of the window, in %."""


def read(ctx):
    if ctx.trace is None:
        return None
    vit = [p.roles["vit"] for p in ctx.built.parts if "vit" in p.roles]
    if not vit:
        return None
    return ctx.trace.idle_pct(ctx.built.module_chip[vit[0]])
