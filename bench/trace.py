"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
directory, with nothing but JAX. ``reduce`` keeps the device operations
that ran inside the harness's ``bench.window`` host span and returns:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices (overlapping or nested operations
  count once);
* ``kernel_s``: each operation's summed device self time (its duration
  less that of the operations nested in it, as a loop's body is in the
  loop) over all devices, keyed by the HLO instruction's name without
  its ``.N`` suffix (a Pallas kernel's instruction carries the kernel's
  name, e.g. ``%fused_ffn.6 = f32[...] custom-call(...)``);
* ``gaps``: the longest idle stretches of the first device, each named
  by the innermost event of the host's main thread in progress at its
  middle or, in a trace without host events, by the device operation
  that ended it (``before <op>``).

A trace with no TPU plane (a CPU run, as in the tests) takes the XLA
CPU client's operations, those that carry an ``hlo_op``, as device 0.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

__all__ = ["Event", "Trace", "Reduced", "load", "reduce", "self_times",
           "base_name"]

WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_CPU_CLIENT = "tf_XLA"
_MAIN_THREAD = ("python", "main")
_SUFFIX = re.compile(r"\.\d+$")
_INSTRUCTION = re.compile(r"^%?([^\s=]+)")


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # ordinal -> [Event]
    host: list = field(default_factory=list)      # [Event]


def base_name(name: str) -> str:
    """``%fused_ffn.6 = f32[...] custom-call(...)`` -> ``fused_ffn``."""
    m = _INSTRUCTION.match(name)
    return _SUFFIX.sub("", m.group(1) if m else name)


def load(directory) -> Trace:
    """The device operations and host events of the newest trace under
    ``directory``."""
    from jax.profiler import ProfileData
    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    pd = ProfileData.from_file(str(files[-1]))
    tr = Trace()
    cpu_ops: list[Event] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            evs = [Event(e.name, e.start_ns, e.duration_ns)
                   for line in plane.lines if line.name == _OPS_LINE
                   for e in line.events]
            tr.devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name.startswith(_MAIN_THREAD):
                    tr.host.extend(Event(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events)
                elif line.name.startswith(_CPU_CLIENT):
                    cpu_ops.extend(
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events
                        if any(k == "hlo_op" for k, _ in e.stats))
    if not tr.devices and cpu_ops:
        tr.devices[0] = cpu_ops
    return tr


def _merged(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events) -> list[tuple[Event, float]]:
    """Each event with its duration less its nested events' union."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    own = {id(e): e.dur_ns for e in evs}
    stack: list[Event] = []
    for e in evs:
        while stack and stack[-1].end_ns <= e.start_ns:
            stack.pop()
        if stack:                      # nested in the innermost open event
            own[id(stack[-1])] -= min(e.end_ns, stack[-1].end_ns) - \
                e.start_ns
        stack.append(e)
    return [(e, own[id(e)]) for e in evs]


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    kernel_s: dict
    gaps: list            # [(label, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.kernel_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:top]]}


def _label(host: list, ops: list, start: float, end: float) -> str:
    t = (start + end) / 2
    best = None
    for e in host:
        if e.start_ns <= t <= e.end_ns and e.name != WINDOW_SPAN and (
                best is None or e.dur_ns < best.dur_ns):
            best = e
    if best is not None:
        return best.name
    after = [e for e in ops if e.start_ns >= end]
    if after:
        return "before " + base_name(min(after,
                                         key=lambda e: e.start_ns).name)
    return "(no host event)"


def reduce(tr: Trace, n_devices: int | None = None, top: int = 10,
           window_s: float | None = None) -> Reduced:
    """``window_s``, for a trace without the ``bench.window`` host span
    (one recorded with the host tracer off), is the traced stretch's
    length on the host's clock; the device operations' extent, which
    leaves out host-only time at its two ends, stands in without it."""
    ords = sorted(tr.devices)[:n_devices]
    if not ords:
        raise ValueError("the trace holds no device plane")
    windows = [e for e in tr.host if e.name == WINDOW_SPAN]
    if windows:
        w = max(windows, key=lambda e: e.dur_ns)
        lo, hi = w.start_ns, w.end_ns
    else:
        # no host span: idle gaps are sought within the device
        # operations' extent
        evs = [e for o in ords for e in tr.devices[o]]
        if not evs:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host "
                             f"span and no device operation")
        lo = min(e.start_ns for e in evs)
        hi = max(e.end_ns for e in evs)
    busy, kernel = [], {}
    first_union = None
    for o in ords:
        clipped = [(max(e.start_ns, lo), min(e.end_ns, hi))
                   for e in tr.devices[o] if e.end_ns > lo and
                   e.start_ns < hi]
        merged = _merged(clipped)
        if first_union is None:
            first_union = merged
        busy.append(sum(e - s for s, e in merged))
        inside = [Event(e.name, max(e.start_ns, lo),
                        min(e.end_ns, hi) - max(e.start_ns, lo))
                  for e in tr.devices[o] if e.end_ns > lo and e.start_ns < hi]
        for e, own in self_times(inside):
            k = base_name(e.name)
            kernel[k] = kernel.get(k, 0.0) + own * 1e-9
    edges = [lo] + [x for iv in first_union for x in iv] + [hi]
    holes = sorted(((edges[i + 1] - edges[i], edges[i])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]), reverse=True)[:top]
    gaps = [(_label(tr.host, tr.devices[ords[0]], s, s + d), d * 1e-9)
            for d, s in holes]
    if windows or window_s is None:
        window_s = (hi - lo) * 1e-9
    else:                 # two clocks: never shorter than the device's span
        window_s = max(window_s, (hi - lo) * 1e-9)
    return Reduced(window_s=window_s,
                   busy_s=sum(busy) / len(busy) * 1e-9,
                   kernel_s=kernel, gaps=gaps)
