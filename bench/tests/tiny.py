"""Tiny-width overrides that let a cell run on the CPU in a test: Opto-ViT
Tiny's widths (d 192, 3 heads, d_ff 768) at 2 layers and 64 px (16
patches), two cameras; the 1000-class head is kept, so the gaps compared
have the cell's distribution of near ties. The kept patches and the loop
come from the cell's own files, so a new cell needs no edit here."""

from __future__ import annotations

from pathlib import Path

from bench import run

ROOT = Path(__file__).resolve().parents[2]
PATCHES = 16            # 64 px at patch 16
# the server's default ladder, as fractions of the patches
LADDER = (0.25, 0.5, 0.75, 1.0)


def keep_at_tiny(force_bucket: float, fractions=LADDER) -> int:
    """The rung a pinned keep ratio routes to over 16 patches: the
    smallest rung at or above the ratio's share of the patches."""
    rungs = sorted({min(PATCHES, max(1, round(f * PATCHES)))
                    for f in fractions})
    budget = round(force_bucket * PATCHES)
    return next((k for k in rungs if k >= budget), rungs[-1])


def overrides(workload: str, root: Path = ROOT, **config) -> dict:
    c = run.load_cell(workload, root)
    server = c["cell"]["server"]
    o = {"config": {"n_layers": 2, "d_model": 192, "n_heads": 3,
                    "d_ff": 768, "img_size": 64, **config},
         "traffic": {"cameras": 2, "ring_frames": 32, "cut_every": 16},
         "cell": {"sample_frames": 64,
                  "keep_patches": keep_at_tiny(
                      server["force_bucket"],
                      server.get("bucket_fractions", LADDER))}}
    if c["traffic"].loop == "open":
        o["traffic"].update(clip_frames=16, clips_per_s=4.0)
    else:
        o["traffic"].update(session_frames=16)
    return o
