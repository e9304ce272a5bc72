"""The serving loop's own spans against the device trace, and a span
stretch of a cell run on the chip.

``reduce`` takes a trace whose host events are the program's spans: on
the chip, the span ring of a stretch served under the benchmark's
device-only trace, read onto the trace's clock by ``ring_trace`` (the
profiler's host tracer, even at level 1, slowed the serving loop two to
five times on a TPU v5e). It splits the ``bench.window`` span into the
device's busy time and its idle time, each instant of idle time named by
the innermost ``serve.*`` span open on the host, or ``outside serve``
where no serve call was open (the harness between calls). Other host
events name nothing, nor does ``serve.session``, which spans rounds.
Busy and the idle shares add up to the whole stretch on every device by
construction; shares are averaged over the devices. It also sums each
``serve.*`` span's host self time, and each XLA module's device time
(``module_seconds``), by the stable program names ``jit_<name>`` of
``serving/server.py``.

Run as a script, it runs one cell's traffic on the chip and prints what
the benchmark's own run cannot give (its last stdout line is JSON):

    python bench/spans.py --workload <cell> --seed <n> [--seconds 20]
        [--pairs 1] [--ring-windows 0] [--out chiprun_out/spans]

It sets the cell up as ``bench/run.py`` does, then serves ``--pairs``
times a window with spans off and one with the span ring on and no
profiler (in the order off, on, on, off, ...); ``--ring-windows`` more
windows with the ring on, each ring dumped and every host stall over
half a second placed (inside which serve span, or between serve calls);
and last a span stretch of ``TRACE_SLICE_S``, whose reduction it prints
with its ten longest idle gaps and the stretch's XLA module and
operation times.
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import time
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import trace as tr  # noqa: E402

__all__ = ["OUTSIDE", "SpanReduced", "reduce", "module_seconds",
           "load_modules", "span_slice", "ring_trace"]

PREFIX = "serve."
SESSION = "serve.session"      # spans rounds: names no instant
OUTSIDE = "outside serve"
_MODULES_LINE = "XLA Modules"
STALL_S = 0.5


@dataclass
class SpanReduced:
    window_s: float
    busy_share: float     # of the stretch, averaged over devices
    idle_share: dict      # innermost serve span (or OUTSIDE) -> share
    self_s: dict          # serve span -> summed host self time, s
    gaps: list            # [(label, s)] first device, longest first

    def total(self) -> float:
        return self.busy_share + sum(self.idle_share.values())


def _segments(spans: list, lo: float, hi: float) -> list:
    """[(start, end, label)] tiling [lo, hi]: the innermost of the nested
    ``spans`` open there, or ``OUTSIDE``."""
    out: list = []
    stack: list = []
    t = lo

    def upto(x):
        nonlocal t
        x = min(max(x, lo), hi)
        if x > t:
            out.append((t, x, stack[-1].name if stack else OUTSIDE))
            t = x

    for e in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1].end_ns <= e.start_ns:
            upto(stack[-1].end_ns)
            stack.pop()
        upto(e.start_ns)
        stack.append(e)
    while stack:
        upto(stack[-1].end_ns)
        stack.pop()
    upto(hi)
    return out


def _holes(busy: list, lo: float, hi: float) -> list:
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _label_at(segs: list, t: float) -> str:
    for s, e, name in segs:
        if s <= t < e:
            return name
    return OUTSIDE


def reduce(trace: tr.Trace, n_devices: int | None = None,
           top: int = 10) -> SpanReduced:
    windows = [e for e in trace.host if e.name == tr.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {tr.WINDOW_SPAN!r} span")
    w = max(windows, key=lambda e: e.dur_ns)
    lo, hi = w.start_ns, w.end_ns
    spans = [e for e in trace.host if e.name.startswith(PREFIX)
             and e.name != SESSION and e.end_ns > lo and e.start_ns < hi]
    segs = _segments(spans, lo, hi)
    ords = sorted(trace.devices)[:n_devices]
    if not ords:
        raise ValueError("the trace holds no device plane")
    busy, idle = 0.0, {}
    first_holes = None
    for o in ords:
        merged = tr._merged((max(e.start_ns, lo), min(e.end_ns, hi))
                            for e in trace.devices[o]
                            if e.end_ns > lo and e.start_ns < hi)
        busy += sum(e - s for s, e in merged)
        holes = _holes(merged, lo, hi)
        if first_holes is None:
            first_holes = holes
        i = 0
        for hs, he in holes:            # both lists ordered in time
            while i < len(segs) and segs[i][1] <= hs:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < he:
                s, e, name = segs[j]
                idle[name] = idle.get(name, 0.0) + min(e, he) - max(s, hs)
                j += 1
    span_ns = (hi - lo) * len(ords)
    self_s: dict = {}
    for e, own in tr.self_times(spans):
        self_s[e.name] = self_s.get(e.name, 0.0) + own * 1e-9
    longest = sorted(first_holes, key=lambda h: h[0] - h[1])[:top]
    return SpanReduced(
        window_s=(hi - lo) * 1e-9, busy_share=busy / span_ns,
        idle_share={k: v / span_ns for k, v in idle.items()},
        self_s=self_s,
        gaps=[(_label_at(segs, (s + e) / 2), (e - s) * 1e-9)
              for s, e in longest])


def load_modules(directory) -> dict:
    """{device ordinal: [Event]} of each XLA module's execution on the
    device: a TPU's ``XLA Modules`` line, or, in a trace without a TPU
    plane, the XLA CPU client's operations named by their ``hlo_module``."""
    from jax.profiler import ProfileData
    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return {}
    out: dict = {}
    cpu: list = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        m = tr._DEVICE_PLANE.match(plane.name)
        if m:
            out[int(m.group(1))] = [
                tr.Event(e.name.split("(")[0], e.start_ns, e.duration_ns)
                for line in plane.lines if line.name == _MODULES_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name.startswith(tr._CPU_CLIENT):
                    for e in line.events:
                        mod = dict(e.stats).get("hlo_module")
                        if mod is not None:
                            cpu.append(tr.Event(str(mod), e.start_ns,
                                                e.duration_ns))
    if not out and cpu:
        out[0] = cpu
    return out


def module_seconds(modules: dict, n_devices: int | None = None) -> dict:
    """Each module's device time (the union of its intervals), summed
    over the first ``n_devices`` devices, in s."""
    out: dict = {}
    for o in sorted(modules)[:n_devices]:
        by: dict = {}
        for e in modules[o]:
            by.setdefault(e.name, []).append((e.start_ns, e.end_ns))
        for name, ivs in by.items():
            out[name] = out.get(name, 0.0) + sum(
                e - s for s, e in tr._merged(ivs)) * 1e-9
    return out


# -- the chip run ------------------------------------------------------------

def _window(server, streams, t, seconds, seed):
    from bench import loads
    if t.loop == "closed":
        return loads.closed_loop(server, streams, t, seconds,
                                 first=t.frames_per_session)
    return loads.open_loop(server, streams, t, seconds, seed)


def _rate(served) -> float:
    return served.frames / served.window_s


def span_slice(server, streams, t, seed: int, trace_dir: Path):
    """``TRACE_SLICE_S`` of the traffic with the spans on (the ring cleared
    first) under the profiler at ``bench/run.py``'s tracer levels, so that
    the host serves at its untraced pace; the ring holds the spans and the
    ``bench.window`` span."""
    import jax
    from bench import run
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    for k, v in run.TRACER_LEVELS[jax.devices()[0].platform].items():
        setattr(opts, k, v)
    server.spans.ring.clear()
    server.spans.enable()
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with server.spans.span(tr.WINDOW_SPAN):
        served = _window(server, streams, t, run.TRACE_SLICE_S, seed)
    jax.profiler.stop_trace()
    server.spans.disable()
    return served


def ring_trace(ring, directory) -> tr.Trace:
    """The trace under ``directory`` with the ring's spans as its host
    events: a ring stamps the profiler's clock, and the trace's events
    count from the session's ``profile_start_time``."""
    from jax.profiler import ProfileData
    trace = tr.load(directory)
    pb = sorted(Path(directory).rglob("*.xplane.pb"),
                key=lambda p: p.stat().st_mtime)[-1]
    start = next(int(v) for plane in ProfileData.from_file(str(pb)).planes
                 for k, v in plane.stats if k == "profile_start_time")
    trace.host = [tr.Event(s.name, s.start_ns - start, s.end_ns - s.start_ns)
                  for s in ring]
    return trace


def stalls(ring: list, late_s: list, t0_ns: int) -> list:
    """Each host stall over ``STALL_S`` in an open-loop window, found as a
    serve call that started that late after its first clip fell due (the
    ring's ``serve.call`` spans and ``late_s`` are one a call, in order),
    and placed by the ring: the innermost span over ``STALL_S`` in the
    call before it, or between the two calls."""
    calls = sorted((s for s in ring if s.name == "serve.call"),
                   key=lambda s: s.start_ns)
    out = []
    for j, late in enumerate(late_s):
        if late <= STALL_S or j >= len(calls):
            continue
        prev = calls[j - 1] if j else None
        at = {"call": j, "late_s": late,
              "at_s": (calls[j].start_ns - t0_ns) * 1e-9}
        if prev is not None and (prev.end_ns - prev.start_ns) * 1e-9 > \
                STALL_S:
            inner = [s for s in ring if s.name not in ("serve.call",
                                                       "serve.session")
                     and s.start_ns >= prev.start_ns
                     and s.end_ns <= prev.end_ns
                     and (s.end_ns - s.start_ns) * 1e-9 > STALL_S]
            where = min(inner, key=lambda s: s.end_ns - s.start_ns,
                        default=prev)
            at.update(inside=where.name, id=where.id,
                      span_s=(where.end_ns - where.start_ns) * 1e-9)
        else:
            since = prev.end_ns if prev is not None else t0_ns
            at["between_calls_s"] = (calls[j].start_ns - since) * 1e-9
        out.append(at)
    return out


def main(argv=None) -> int:
    from bench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--pairs", type=int, default=1)
    ap.add_argument("--ring-windows", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/spans")
    args = ap.parse_args(argv)
    out = Path(args.out) / args.workload
    out.mkdir(parents=True, exist_ok=True)
    c = run.load_cell(args.workload)
    st = run.setup_cell(c, args.seed)
    server, streams, t = st["server"], st["streams"], c["traffic"]
    res: dict = {"workload": args.workload, "seed": args.seed,
                 "device": st["devs"][0].device_kind,
                 "chips": len(st["devs"])}

    def key(served):
        if t.loop == "closed":
            return _rate(served)
        return 1e3 * statistics.median(served.clip_latency_s)

    off, on = [], []
    for i in range(2 * args.pairs):
        ring_on = i % 4 in (1, 2)
        if ring_on:
            server.spans.enable()
        before = server.spans.counts()
        served = _window(server, streams, t, args.seconds, args.seed + i)
        server.spans.disable()
        (on if ring_on else off).append(key(served))
        if not ring_on and "counters_per_frame" not in res:
            res["counters_per_frame"] = {
                k: (v - before[k]) / served.frames
                for k, v in server.spans.counts().items()}
    res["metric"] = "frames_per_s" if t.loop == "closed" else \
        "clip_latency_p50_ms"
    res["spans_off"], res["ring_on"] = off, on

    found = []
    for i in range(args.ring_windows):
        server.spans.ring.clear()
        server.spans.enable()
        t0 = server.spans.now()
        served = _window(server, streams, t, args.seconds,
                         args.seed + 100 + i)
        server.spans.disable()
        server.spans.dump(out / f"ring{i}.json")
        lat = sorted(served.clip_latency_s)
        found.append({"window": i, "p50_ms": 1e3 * statistics.median(lat),
                      "max_ms": 1e3 * lat[-1],
                      "max_late_s": max(served.late_s, default=0.0),
                      "stalls": stalls(list(server.spans.ring),
                                       served.late_s, t0)})
    if found:
        res["ring_windows"] = found

    # the span stretch, traced into the benchmark's own trace directory
    n = len(st["devs"])
    traced = span_slice(server, streams, t, args.seed + 7, run.TRACE_DIR)
    trace = ring_trace(list(server.spans.ring), run.TRACE_DIR)
    red = reduce(trace, n)
    ops = tr.reduce(trace, n_devices=n)
    for label, s in red.gaps:
        print(f"idle gap {1e3 * s:.3f} ms under {label}", file=sys.stderr)
    res["stretch"] = {
        "window_s": red.window_s, "frames": traced.frames,
        "rate": key(traced), "busy_pct": 100 * red.busy_share,
        "idle_pct": {k: 100 * v for k, v in sorted(
            red.idle_share.items(), key=lambda kv: -kv[1])},
        "total_pct": 100 * red.total(),
        "self_us_per_frame": {k: 1e6 * v / traced.frames
                              for k, v in sorted(red.self_s.items())},
        "gaps_ms": [[label, 1e3 * s] for label, s in red.gaps],
        "module_s": module_seconds(load_modules(run.TRACE_DIR), n),
        "ops_self_s": dict(sorted(ops.kernel_s.items(),
                                  key=lambda kv: -kv[1])[:12]),
        "ops_busy_s": ops.busy_s}
    (out / "result.json").write_text(json.dumps(res, indent=1))
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    t_start = time.perf_counter()
    rc = main()
    print(f"bench/spans.py took {time.perf_counter() - t_start:.1f} s",
          file=sys.stderr)
    sys.exit(rc)
