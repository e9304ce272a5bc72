"""Real frames per encode launch over the window (``server.flush_log``):
how full the serving loop packs its micro-batches."""


def read(ctx):
    flushes = ctx["served"].flushes
    if not flushes:
        return None
    return sum(n for _, n in flushes) / len(flushes)
