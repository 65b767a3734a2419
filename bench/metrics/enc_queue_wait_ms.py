"""Mean queue wait at the encoder modules: the program's ``admission``
spans of encoder stages, in ms."""


def read(ctx):
    enc = set(ctx.built.encoders)
    d = [s.dur for s in ctx.readings.spans
         if s.phase == "admission" and s.name in enc]
    return 1e3 * sum(d) / len(d) if d else None
