"""Share of the device's busy time that MGNet's score program takes, in
%: the device time of its XLA module ``jit_mgnet_score`` (the name
``serving/server.py`` gives the gate's jit) in the device-only traced
stretch, averaged over the chips, over their busy time there. Nothing
when the trace names no such module (a program whose jits are unnamed)."""

from bench import run, spans

MODULE = "jit_mgnet_score"


def read(ctx):
    t = spans.module_seconds(spans.load_modules(run.TRACE_DIR),
                             ctx["chips"]).get(MODULE)
    if not t or not ctx["trace"].busy_s:
        return None
    return 100.0 * t / ctx["chips"] / ctx["trace"].busy_s
