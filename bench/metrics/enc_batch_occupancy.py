"""Mean stages per encoder launch over the window: the program's
``serve.batch_occupancy`` histograms of the encoder modules, less their
state at the window's start."""


def read(ctx):
    enc = set(ctx.built.encoders)
    count = total = 0.0
    for inst in ctx.built.dep.scheduler.metrics.instruments(
            "serve.batch_occupancy"):
        if inst.labels.get("module") not in enc:
            continue
        c0, s0 = ctx.readings.hist0.get(inst.key, (0, 0.0))
        count += inst.count - c0
        total += inst.sum - s0
    return total / count if count else None
