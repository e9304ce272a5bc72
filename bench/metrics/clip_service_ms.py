"""Median duration of the serve call that served a clip, in ms (harness
clock)."""

import statistics


def read(ctx):
    w = ctx["served"].clip_service_s
    return 1e3 * statistics.median(w) if w else None
