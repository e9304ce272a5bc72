"""Operations and bytes that the served frames need, from the layers'
mathematics at the configuration's shapes.

Nothing here looks at a kernel. Rows are real rows only (a flush's
padding frames, a score launch's zero frames and the kernels' tile
padding are never counted), and each matmul is counted once (a fused
kernel's recompute is not work the frame needs). So a later
implementation that drops padding or recompute reads a higher share,
and none can read above 100%.

Bytes are what one launch must move at least: its int8 activation and
weight operands once, its float32 result once (and a float kernel's
float32 operands once). Weights are counted once per launch, because a
launch cannot avoid reading them.

Work is kept by the Pallas kernel name the trace shows. The counts here
are the parts every model served behind the RoI gate shares: the patch
embed and MGNet, whose int8 linears run as ``MATMUL``, and MGNet's float
attention under ``other`` (needed work outside any kernel, which only
the whole-step share counts). A model module (``bench/models/``) counts
its encoder and head from these pieces, under the kernel names it
declares in its ``KERNELS``.
"""

from __future__ import annotations

from dataclasses import dataclass, field

__all__ = ["Tally", "Work", "matmul_int8", "attention_float", "embed_work",
           "mgnet_work", "MATMUL", "F32"]

MATMUL = "photonic_matmul"
F32 = 4


@dataclass
class Tally:
    """Operations of one kind (``int8`` or ``float``) and bytes."""

    int8_ops: float = 0.0
    float_ops: float = 0.0
    bytes: float = 0.0

    def add(self, other: "Tally", times: float = 1.0) -> None:
        self.int8_ops += other.int8_ops * times
        self.float_ops += other.float_ops * times
        self.bytes += other.bytes * times

    def seconds_at_peak(self, peaks) -> float:
        """Least time the chip could take: the larger of the compute bound
        and the memory bound."""
        compute = (self.int8_ops / peaks.ops_per_s("int8")
                   + self.float_ops / peaks.ops_per_s("float"))
        return max(compute, self.bytes / peaks.hbm_bytes_per_s)

    def compute_seconds_at_peak(self, peaks) -> float:
        return (self.int8_ops / peaks.ops_per_s("int8")
                + self.float_ops / peaks.ops_per_s("float"))


@dataclass
class Work:
    """Needed work per kernel name (plus ``other``)."""

    by_kernel: dict = field(default_factory=dict)

    def add(self, kernel: str, t: Tally, times: float = 1.0) -> None:
        self.by_kernel.setdefault(kernel, Tally()).add(t, times)

    def merge(self, other: "Work", times: float = 1.0) -> None:
        for k, t in other.by_kernel.items():
            self.add(k, t, times)

    def total(self) -> Tally:
        out = Tally()
        for t in self.by_kernel.values():
            out.add(t)
        return out


def matmul_int8(m: int, k: int, n: int) -> Tally:
    """(m, k) int8 @ (k, n) int8 -> (m, n) float32, per-channel scales."""
    return Tally(int8_ops=2.0 * m * k * n,
                 bytes=m * k + k * n + F32 * (m * n + n))


def attention_float(n: int, d: int) -> Tally:
    """softmax(QK^T)V over all heads of one sequence of n tokens, float32
    operands and result: two (n, d, n)-shaped contractions."""
    return Tally(float_ops=2.0 * 2.0 * n * n * d, bytes=F32 * 4 * n * d)


def embed_work(cfg: dict, frames: int) -> Work:
    """Patch embedding of ``frames`` whole frames (every patch)."""
    n = (cfg["img_size"] // cfg["patch"]) ** 2
    p_in = 3 * cfg["patch"] ** 2
    w = Work()
    w.add(MATMUL, matmul_int8(frames * n, p_in, cfg["d_model"]))
    return w


def mgnet_work(cfg: dict, frames: int) -> Work:
    """MGNet scoring of ``frames`` real frames: patch embed, one block over
    [cls] + patches, the cls-query score and the linear region head."""
    n = (cfg["img_size"] // cfg["patch"]) ** 2
    p_in = 3 * cfg["patch"] ** 2
    d = cfg["mgnet_embed"]
    dff = int(d * cfg["mgnet_mlp_ratio"])
    t = n + 1
    w = Work()
    for m, k, nn in ((n, p_in, d),           # patch embed
                     (t, d, 3 * d),          # wqkv
                     (t, d, d),              # wo
                     (t, d, dff),            # w1
                     (t, dff, d),            # w2
                     (1, d, d),              # score q of [cls]
                     (n, d, d),              # score keys
                     (1, n, n)):             # region head
        w.add(MATMUL, matmul_int8(frames * m, k, nn))
    att = attention_float(t, d)
    att.add(Tally(float_ops=2.0 * n * d))    # q_cls . K^T
    w.add("other", att, frames)
    return w
