"""``fused_ffn``'s share of its roofline, in %: the least time the chip
could take for the work the served frames need of this kernel (the
cell's model module's ``frame_work``, ``bench/models/``), over the
kernel's summed device time in the trace. Nothing when the trace
shows no such kernel."""

KERNEL = "fused_ffn"


def read(ctx):
    t = ctx["trace"].kernel_s.get(KERNEL)
    work = ctx["work"].by_kernel.get(KERNEL)
    if not t or work is None or ctx["peaks"] is None:
        return None
    return 100.0 * work.seconds_at_peak(ctx["peaks"]) / t
