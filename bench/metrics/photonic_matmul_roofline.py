"""``photonic_matmul``'s share of its roofline, in %: the least time the chip
could take for the work the served frames need of this kernel
(``bench/ops.py``), over the kernel's summed device time in the trace.
Nothing when the trace shows no such kernel."""

KERNEL = "photonic_matmul"


def read(ctx):
    t = ctx["trace"].kernel_s.get(KERNEL)
    work = ctx["work"].by_kernel.get(KERNEL)
    if not t or work is None or ctx["peaks"] is None:
        return None
    return 100.0 * work.seconds_at_peak(ctx["peaks"]) / t
