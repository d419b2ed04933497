"""Programs traced, lowered and loaded from the persistent compilation
cache inside the timed window. Each is host time between device work: a
program the caller does not keep compiled is rebuilt on every call."""


def read(ctx):
    return float(ctx.window.counters["cache_hits"])
