"""Idle share of the chip that holds the generative decoder, over the
traced part of the window: 100 less the union of device operations'
time over the traced seconds, in %."""


def read(ctx):
    if ctx.trace is None or ctx.built.decoder is None:
        return None
    chip = ctx.built.module_chip[ctx.built.decoder]
    return ctx.trace.idle_pct(chip)
