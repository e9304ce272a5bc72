"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind missing here is an error, never a
default: a roofline share against the wrong chip means nothing."""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Peaks", "PEAKS", "peaks_for"]


@dataclass(frozen=True)
class Peaks:
    int8_ops_per_s: float       # MXU int8 multiply-accumulates, 2 ops each
    bf16_flops_per_s: float     # MXU bf16 (float dots at default precision)
    hbm_bytes_per_s: float
    source: str

    def ops_per_s(self, kind: str) -> float:
        """Peak rate for ``kind`` ("int8" or "float")."""
        if kind == "int8":
            return self.int8_ops_per_s
        if kind == "float":
            return self.bf16_flops_per_s
        raise ValueError(f"unknown op kind {kind!r}")


_V5E = Peaks(int8_ops_per_s=393e12, bf16_flops_per_s=197e12,
             hbm_bytes_per_s=819e9,
             source="Google Cloud TPU documentation, 'TPU v5e' (per chip: "
                    "197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at "
                    "819 GB/s)")

PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": _V5E,        # what JAX reports for a v5e chip
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
