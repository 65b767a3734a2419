"""Peak share of the KV page pool in use during the window: the largest
``pages_live`` tag of a ``decode_tick`` span over the pool's pages, in %."""


def read(ctx):
    live = [p for _, _, _, p in ctx.readings.ticks]
    if not live:
        return None
    return 100.0 * max(live) / ctx.built.serve["decode_pages"]
