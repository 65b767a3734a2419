"""Mean wall time of one batched decode tick (the program's
``decode_tick`` span, taken once per tick: dispatch to every row's token
on the host), in ms."""


def read(ctx):
    d = [t1 - t0 for t0, t1, _, _ in ctx.readings.ticks]
    return 1e3 * sum(d) / len(d) if d else None
