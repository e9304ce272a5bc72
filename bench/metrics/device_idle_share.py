"""Share of the traced stretch in which no operation ran on the device,
in %, averaged over the chips (``bench/trace.py``). The stretch is
traced with the host tracer off, so that the host serves at its
untraced pace."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share
