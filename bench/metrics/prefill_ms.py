"""Mean wall time of the decode stream's batch-1 prefill (the program's
``prefill`` span: dispatch to first token on the host), in ms."""


def read(ctx):
    d = [s.dur for s in ctx.readings.spans if s.phase == "prefill"]
    return 1e3 * sum(d) / len(d) if d else None
