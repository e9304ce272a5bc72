"""95th percentile of the clips' latency in the window, from each clip's
due time until the serve call that served it returned, in ms (harness
clock). A host stall of a second lifts it several times over, so it is
read here and not bounded."""

import statistics


def read(ctx):
    lat = ctx["served"].clip_latency_s
    if len(lat) < 2:
        return None
    return 1e3 * statistics.quantiles(lat, n=20, method="inclusive")[18]
