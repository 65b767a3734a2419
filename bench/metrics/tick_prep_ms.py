"""Mean host time of a decode tick before its step runs on the device:
the program's ``tick.form`` span (pages extended, token array built,
tables copied) plus its ``tick.dispatch`` span (three host-to-device
copies and the step's asynchronous dispatch), per window tick, in ms."""


def read(ctx):
    form = [s.dur for s in ctx.readings.spans
            if s.name == ctx.built.decoder and s.phase == "tick.form"]
    dispatch = [s.dur for s in ctx.readings.spans
                if s.name == ctx.built.decoder and s.phase == "tick.dispatch"]
    if not dispatch:
        return None
    return 1e3 * (sum(form) + sum(dispatch)) / len(dispatch)
