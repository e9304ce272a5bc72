"""Tiny overrides that let a cell run on the CPU in a test: the widths,
depth and image size of the cell's model module's ``TINY``, two cameras.
The patch count follows from ``TINY``'s image size and the patch; the
kept patches and the loop come from the cell's own files, so a new cell
or model needs no edit here."""

from __future__ import annotations

from pathlib import Path

from bench import run

ROOT = Path(__file__).resolve().parents[2]
# the server's default ladder, as fractions of the patches
LADDER = (0.25, 0.5, 0.75, 1.0)


def patch_count(cfg: dict) -> int:
    return (cfg["img_size"] // cfg["patch"]) ** 2


def rungs(patches: int, fractions=LADDER) -> list:
    """The ladder's rungs over ``patches`` patches."""
    return sorted({min(patches, max(1, round(f * patches)))
                   for f in fractions})


def keep_at_tiny(force_bucket: float, patches: int,
                 fractions=LADDER) -> int:
    """The rung a pinned keep ratio routes to over ``patches`` patches:
    the smallest rung at or above the ratio's share of the patches."""
    budget = round(force_bucket * patches)
    ladder = rungs(patches, fractions)
    return next((k for k in ladder if k >= budget), ladder[-1])


def tiny_patches(workload: str, root: Path = ROOT) -> int:
    """The patches a frame of the cell has at its model's ``TINY`` size."""
    c = run.load_cell(workload, root)
    return patch_count({**c["cfg"], **c["model"].TINY})


def overrides(workload: str, root: Path = ROOT, **config) -> dict:
    c = run.load_cell(workload, root)
    server = c["cell"]["server"]
    tiny = {**c["model"].TINY, **config}
    o = {"config": tiny,
         "traffic": {"cameras": 2, "ring_frames": 32, "cut_every": 16},
         "cell": {"sample_frames": 64,
                  "keep_patches": keep_at_tiny(
                      server["force_bucket"],
                      patch_count({**c["cfg"], **tiny}),
                      server.get("bucket_fractions", LADDER))}}
    if c["traffic"].loop == "open":
        o["traffic"].update(clip_frames=16, clips_per_s=4.0)
    else:
        o["traffic"].update(session_frames=16)
    return o
