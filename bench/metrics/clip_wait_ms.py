"""Median time a clip waited from its due time until the serve call that
took it started, in ms (harness clock)."""

import statistics


def read(ctx):
    w = ctx["served"].clip_wait_s
    return 1e3 * statistics.median(w) if w else None
