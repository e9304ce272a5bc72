"""Temporal RoI-mask reuse: the near-sensor trick that makes MGNet ~free.

Consecutive video frames are highly correlated, so the RoI mask rarely
changes between them. The cache re-runs MGNet only when

  * ``refresh`` frames have elapsed since the last scoring (staleness bound),
  * or the cheap frame-delta signal (mean |frame - last_scored_frame|)
    exceeds ``delta_threshold`` — motion or a scene cut;

otherwise the cached region scores are reused verbatim. The decision walk is
sequential (frame i's reference is the most recent *scored* frame before it)
and runs on the host, in numpy: each delta is computed into one scratch
buffer the cache owns, bitwise what ``frame_delta`` returns, and the
reference frame is a host array. The frames that do need scoring are
batched into a single MGNet call per ingest chunk, padded to the chunk, so
the device sees one static-shaped score launch instead of per-frame
dispatches.

Where the arrays live. Given the chunk's device view (``device_frames``),
the gate keeps its scores on the device: MGNet's padded input is gathered
there from the frames already put (``gate_inputs``), each frame's scores
are selected there from the cached reference row and the fresh rows
(``gate_scores``), and the reference scores stay a device array. Nothing
crosses to the host, so the gate never waits on the device. A caller that
routes on the scores pulls them with ``host_scores``: once per scored
chunk, since a chunk that scored nothing repeats the reference row, whose
host copy is kept. ``state_dict`` pulls the reference scores for a
checkpoint. Without a device view the gate is all numpy (its padded input
in a chunk-sized buffer the cache reuses), the path the device one is
tested against.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["TemporalMaskCache", "GateOps", "gate_inputs", "gate_scores"]


def gate_inputs(frames, rows):
    """MGNet's padded input: ``frames[rows]``, a zero frame where a row is
    out of range (the pad past the chunk's scored frames)."""
    return jnp.take(frames, rows, axis=0, mode="fill", fill_value=0)


def _select(xp, ref, fresh, sel):
    """Row ``sel[i]`` of ``[ref; fresh]`` (float32) for each frame; either
    part may be absent."""
    parts = [p for p in (None if ref is None else ref[None], fresh)
             if p is not None]
    return xp.concatenate([xp.asarray(p, xp.float32) for p in parts])[sel]


def gate_scores(ref, fresh, sel):
    """Each frame's region scores, from the reference row ``ref`` (N,) and
    the fresh rows ``fresh`` (C, N) by ``sel`` (C,), and the last frame's
    row: the next reference."""
    out = _select(jnp, ref, fresh, sel)
    return out, out[-1]


class GateOps(NamedTuple):
    """The gate's device programs, jitted: ``inputs`` (``gate_inputs``)
    and ``scores`` (``gate_scores``)."""

    inputs: Callable
    scores: Callable

    @classmethod
    def build(cls, wrap: Callable = lambda f: f) -> "GateOps":
        """``wrap`` each program before its jit (on a mesh, the server's
        whole-on-each-device ``shard_map``)."""
        return cls(jax.jit(wrap(gate_inputs)), jax.jit(wrap(gate_scores)))


class TemporalMaskCache:
    """Per-stream cached MGNet scores + the frame they were computed on."""

    def __init__(self, refresh: int = 8, delta_threshold: float = 0.15):
        if refresh < 1:
            raise ValueError("refresh must be >= 1")
        self.refresh = refresh
        self.delta_threshold = delta_threshold
        self._ref_frame: np.ndarray | None = None    # last scored frame
        self._ref_scores = None      # its region scores (N,), on the device
        #                              after a device gate, else numpy
        self._ref_host: np.ndarray | None = None     # host copy, once known
        self._ref_idx: int = -(1 << 30)
        self._delta_buf: np.ndarray | None = None    # (1, H, W, 3) float32
        self._sub_buf: np.ndarray | None = None      # host-path MGNet input
        self.scored_frames = 0
        self.reused_frames = 0

    def reset(self) -> None:
        self.__init__(self.refresh, self.delta_threshold)

    def _delta(self, frame: np.ndarray, ref: np.ndarray) -> float:
        """``frame_delta(frame[None], ref)[0]``, bitwise, without a
        temporary: the float32 difference, its absolute value and the same
        mean over the same (1, H, W, 3) shape, in one reused buffer."""
        buf = self._delta_buf
        if buf is None or buf.shape[1:] != frame.shape:
            buf = self._delta_buf = np.empty((1,) + frame.shape, np.float32)
        np.subtract(frame, ref, out=buf[0], dtype=np.float32)
        np.abs(buf, out=buf)
        return float(buf.mean(axis=tuple(range(1, buf.ndim)))[0])

    def _walk(self, frames: np.ndarray, frame_idx: list,
              eligible: np.ndarray) -> np.ndarray:
        """(C,) bool: the frames to score."""
        flags = np.zeros(frames.shape[0], bool)
        ref, ref_idx = self._ref_frame, self._ref_idx
        for i, idx in enumerate(frame_idx):
            if not eligible[i]:
                continue
            if (ref is None or idx - ref_idx >= self.refresh
                    or self._delta(frames[i], ref) > self.delta_threshold):
                flags[i] = True
                ref, ref_idx = frames[i], idx
        return flags

    def _host_input(self, frames: np.ndarray, scored: np.ndarray):
        """MGNet's padded input on the host, in the reused buffer."""
        sub = self._sub_buf
        if (sub is None or sub.shape != frames.shape
                or sub.dtype != frames.dtype):
            sub = self._sub_buf = np.empty_like(frames)
        n = len(scored)
        np.take(frames, scored, axis=0, out=sub[:n], mode="clip")
        sub[n:] = 0
        return sub

    def gate(self, frames, frame_idx, score_fn, eligible=None,
             device_frames=None, ops: GateOps | None = None):
        """RoI-gate one chunk of consecutive frames.

        frames: (C, H, W, 3) host view; frame_idx: (C,) absolute stream
        positions; score_fn: (C, H, W, 3) -> (C, N) region scores (MGNet
        forward), always called on the whole padded chunk (the flagged
        frames first, zero frames after); eligible: optional (C,) bool —
        frames marked False are never scored, never update the reference,
        and don't enter the reuse stats (the engine's beyond-``n_frames``
        tail of a final chunk). Their score rows are cached filler;
        callers must not consume them. device_frames: the same chunk on
        the device; given, MGNet reads it and the scores stay on the
        device, by the jitted programs ``ops``.
        Returns (scores (C, N) float32 — numpy without ``device_frames``,
        a device array with it — and n_scored_this_chunk).
        """
        frames = np.asarray(frames)
        frame_idx = [int(i) for i in np.asarray(frame_idx)]
        c = frames.shape[0]
        eligible = (np.ones(c, bool) if eligible is None
                    else np.asarray(eligible, bool))
        flags = self._walk(frames, frame_idx, eligible)
        scored = np.flatnonzero(flags)
        n_scored = len(scored)

        # frame i reads row sel[i] of [reference; fresh]: the reference
        # until the chunk's first scored frame, then the newest fresh row
        sel = np.cumsum(flags, dtype=np.int32)
        ref = self._ref_scores
        if ref is None:
            if sel[0] == 0:
                raise ValueError("mask cache is empty and no eligible frame "
                                 "was scored — nothing to reuse")
            sel -= 1
        if device_frames is None:
            fresh = (np.asarray(score_fn(self._host_input(frames, scored)),
                                np.float32) if n_scored else None)
            scores = _select(np, ref, fresh, sel)
            last = scores[-1].copy()
        else:
            fresh = None
            if n_scored:
                # padded to the full chunk so ``score_fn`` sees ONE static
                # shape for the whole stream; row c is out of range: zeros
                rows = np.full(c, c, np.int32)
                rows[:n_scored] = scored
                fresh = score_fn(ops.inputs(device_frames, rows))
            scores, last = ops.scores(ref, fresh, sel)

        # persist the newest reference for the next chunk
        if n_scored:
            self._ref_frame = frames[scored[-1]]
            self._ref_scores = last
            self._ref_host = None
            self._ref_idx = frame_idx[scored[-1]]
        self.scored_frames += n_scored
        self.reused_frames += int(eligible.sum()) - n_scored
        return scores, n_scored

    def host_scores(self, scores, n_scored: int) -> tuple[np.ndarray, bool]:
        """The chunk's device ``scores`` (from ``gate``, with its
        ``n_scored``) on the host, and whether they crossed from the device.
        A chunk that scored nothing repeats the reference row, whose host
        copy the cache keeps from the last pull or a restored state: only
        scored chunks cross."""
        if not n_scored and self._ref_host is not None:
            return np.broadcast_to(self._ref_host, scores.shape), False
        out = np.asarray(scores)
        self._ref_host = out[-1]
        return out, True

    @property
    def reuse_rate(self) -> float:
        tot = self.scored_frames + self.reused_frames
        return self.reused_frames / tot if tot else 0.0

    # -- checkpoint/migration ---------------------------------------------

    def state_dict(self) -> dict:
        """Snapshot the gating walk's full state: the reference frame and
        its scores (numpy — device scores are pulled here — or None before
        anything was scored), the reference index, and the reuse counters.
        A restored cache makes the *same* refresh-vs-reuse decision on the
        next frame the original would have — the bitwise-resume
        requirement."""
        return {
            "ref_frame": (None if self._ref_frame is None
                          else np.asarray(self._ref_frame)),
            "ref_scores": (None if self._ref_scores is None
                           else np.asarray(self._ref_scores)),
            "ref_idx": int(self._ref_idx),
            "scored_frames": int(self.scored_frames),
            "reused_frames": int(self.reused_frames),
        }

    def load_state(self, state: dict) -> None:
        self._ref_frame = (None if state["ref_frame"] is None
                           else np.asarray(state["ref_frame"]))
        self._ref_scores = self._ref_host = (
            None if state["ref_scores"] is None
            else np.asarray(state["ref_scores"]))
        self._ref_idx = int(state["ref_idx"])
        self.scored_frames = int(state["scored_frames"])
        self.reused_frames = int(state["reused_frames"])
