"""The one traffic generator: camera frames rendered in set-up, and the
closed- and open-loop windows that drive ``StreamServer.serve()``.

A traffic mix is a JSON file under ``bench/traffic/`` read by
``Traffic.load``; no mix needs code of its own.

Frames follow the program's synthetic ``VideoStream`` (one bright box
drifting over a dark, noisy background, a scene cut every ``cut_every``
frames), copied here so that the traffic cannot move when the program
changes. Each camera renders a ring of frames once, in set-up; a
session's stream serves its frames from that ring, so the window times
serving and not rendering. The ring's length is a multiple of
``cut_every``, so a wrap is a scene cut.

Closed loop: every camera has a backlog. Each serve call takes one
session of ``session_frames`` frames from every camera, and the next
call starts when it returns. Open loop: clips of ``clip_frames`` frames
arrive at the rate fixed in the mix, each a new session. The arrival
times are the same for every seed (exponential quantile gaps at the
mix's rate, in one fixed shuffled order); the seed draws which camera
sends each clip, and the frames and weights. So seeds change what is
served and not when: the latency tail depends on how arrivals bunch.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

__all__ = ["Traffic", "RingStream", "render_ring", "Served", "serve_round",
           "closed_loop", "open_loop", "arrivals"]

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Traffic:
    name: str
    loop: str                    # "closed" or "open"
    cameras: int
    ring_frames: int
    cut_every: int = 32
    noise: float = 0.05
    speed: float = 1.5
    session_frames: int = 0      # closed loop: frames per camera per call
    clip_frames: int = 0         # open loop: frames per clip
    clips_per_s: float = 0.0     # open loop: aggregate arrival rate

    @property
    def frames_per_session(self) -> int:
        return self.session_frames if self.loop == "closed" else \
            self.clip_frames

    @staticmethod
    def load(name: str, root: Path = HERE) -> "Traffic":
        with open(root / "traffic" / f"{name}.json") as f:
            spec = json.load(f)
        t = Traffic(name=name, **spec)
        per = t.frames_per_session
        if t.loop not in ("closed", "open") or per <= 0:
            raise ValueError(f"traffic {name}: bad loop/frames {spec}")
        if t.ring_frames % t.cut_every or t.ring_frames % per:
            raise ValueError(f"traffic {name}: ring_frames must be a "
                             f"multiple of cut_every and of {per}")
        if t.loop == "open" and t.clips_per_s <= 0:
            raise ValueError(f"traffic {name}: open loop needs clips_per_s")
        return t


# -- frames ------------------------------------------------------------------

def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) for w in words]))


def render_ring(t: Traffic, img: int, patch: int, seed: int,
                camera: int) -> np.ndarray:
    """``t.ring_frames`` frames (R, img, img, 3) float32 of one camera."""
    h = img
    out = np.empty((t.ring_frames, h, h, 3), np.float32)
    for seg in range(t.ring_frames // t.cut_every):
        r = _rng(seed, camera, seg)
        bw = int(r.integers(h // 4, h // 2))
        bh = int(r.integers(h // 4, h // 2))
        y0 = float(r.integers(0, h - bh))
        x0 = float(r.integers(0, h - bw))
        ang = float(r.uniform(0, 2 * np.pi))
        vy, vx = t.speed * np.sin(ang), t.speed * np.cos(ang)
        tex = float(r.integers(0, 5))
        span_y, span_x = max(h - bh, 1), max(h - bw, 1)
        noise = _rng(seed, camera, seg, 1 << 20).normal(
            0.0, t.noise, size=(t.cut_every, h, h, 3)).astype(np.float32)
        for off in range(t.cut_every):
            y = int(abs((y0 + vy * off + span_y) % (2 * span_y) - span_y))
            x = int(abs((x0 + vx * off + span_x) % (2 * span_x) - span_x))
            f = noise[off]
            f[y:y + bh, x:x + bw] += 1.0 + 0.2 * tex
            out[seg * t.cut_every + off] = f
    return out


class RingStream:
    """Duck-typed stream for ``StreamServer.add_session``: chunks of
    pre-rendered frames, absolute frame ``start`` read modulo the ring."""

    def __init__(self, ring: np.ndarray):
        self.ring = ring

    def frames(self, start: int, count: int) -> np.ndarray:
        r = len(self.ring)
        s = start % r
        if s + count <= r:
            return self.ring[s:s + count]
        return np.concatenate([self.ring[s:], self.ring[:s + count - r]])

    def chunks(self, chunk: int, start: int = 0):
        while True:
            yield {"frames": self.frames(start, chunk),
                   "frame_idx": np.arange(start, start + chunk,
                                          dtype=np.int32)}
            start += chunk


# -- what a window served ----------------------------------------------------

@dataclass
class Served:
    """Everything a window produced, for the metrics and the check."""

    window_s: float = 0.0
    calls: int = 0
    frames: int = 0
    scored: int = 0
    sessions: list = field(default_factory=list)   # (camera, start, n)
    predictions: list = field(default_factory=list)  # {frame_idx: class}
    flushes: list = field(default_factory=list)    # (bucket k, n_real)
    failed: list = field(default_factory=list)     # sessions short/poisoned
    clip_latency_s: list = field(default_factory=list)
    clip_wait_s: list = field(default_factory=list)
    clip_service_s: list = field(default_factory=list)
    late_s: list = field(default_factory=list)     # generator lateness


def serve_round(server, batch, out: Served):
    """One serve call over ``batch`` = [(camera, stream, start, n)]."""
    sess = [(server.add_session(st, n_frames=n, start=start), cam, start, n)
            for cam, st, start, n in batch]
    results = server.serve()
    out.calls += 1
    out.flushes.extend((k, n_real) for _, k, n_real in server.flush_log)
    for s, cam, start, n in sess:
        r = results.get(s.sid)
        out.sessions.append((cam, start, n))
        preds = {} if r is None else dict(r.predictions)
        out.predictions.append(preds)
        if r is None or r.poisoned or len(preds) != n:
            out.failed.append(len(out.sessions) - 1)
        if r is not None:
            out.frames += r.frames
            out.scored += r.scored_frames


def closed_loop(server, streams: list, t: Traffic, seconds: float,
                first: int = 0) -> Served:
    """Serve calls of one session per camera, from frame ``first`` on,
    until ``seconds`` have passed; the window closes when the last call
    returns."""
    clock = time.perf_counter
    pos = [first] * len(streams)
    out = Served()
    t0 = clock()
    while True:
        batch = []
        for cam, st in enumerate(streams):
            batch.append((cam, st, pos[cam], t.session_frames))
            pos[cam] += t.session_frames
        serve_round(server, batch, out)
        if clock() - t0 >= seconds:
            break
    out.window_s = clock() - t0
    return out


def arrivals(t: Traffic, seconds: float, seed: int
             ) -> tuple[np.ndarray, np.ndarray]:
    """(due times in s from the window's start, camera of each clip)."""
    n = max(1, int(round(t.clips_per_s * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / t.clips_per_s
    due = np.cumsum(_rng(7).permutation(gaps))
    cams = _rng(seed, 7).permutation(np.resize(np.arange(t.cameras), n))
    return due, cams


def open_loop(server, streams: list, t: Traffic, seconds: float,
              seed: int) -> Served:
    """Clips registered as they fall due; each serve call takes every clip
    due at its start. A clip's latency runs from its due time until the
    call that served it returned."""
    clock = time.perf_counter
    due, cams = arrivals(t, seconds, seed)
    pos = [0] * len(streams)
    out = Served()
    i, n = 0, len(due)
    t0 = clock()
    while i < n:
        now = clock() - t0
        if due[i] > now:
            time.sleep(due[i] - now)
            continue
        j = int(np.searchsorted(due, now, side="right"))
        out.late_s.append(now - due[i])
        batch = []
        for c in range(i, j):
            cam = int(cams[c])
            batch.append((cam, streams[cam], pos[cam], t.clip_frames))
            pos[cam] += t.clip_frames
        ts = clock()
        serve_round(server, batch, out)
        te = clock()
        for c in range(i, j):
            out.clip_latency_s.append(te - t0 - due[c])
            out.clip_wait_s.append(ts - t0 - due[c])
            out.clip_service_s.append(te - ts)
        i = j
    out.window_s = clock() - t0
    return out
