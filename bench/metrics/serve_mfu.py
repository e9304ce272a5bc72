"""The whole step's share of the chips' peak, in %: the operations the
frames served in the untraced window need (the cell's model module's
``frame_work``: patch embed, MGNet on the frames it scored, encoder and
head on real rows only), each at the peak of its kind, over that window
times the chips."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    need = ctx["window_work"].total().compute_seconds_at_peak(ctx["peaks"])
    return 100.0 * need / (ctx["served"].window_s * ctx["chips"])
