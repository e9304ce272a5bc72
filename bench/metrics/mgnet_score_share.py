"""Share of served frames that MGNet scored (the rest reused the mask
cache's scores), in %, from the sessions' ``scored_frames``."""


def read(ctx):
    s = ctx["served"]
    if not s.frames:
        return None
    return 100.0 * s.scored / s.frames
