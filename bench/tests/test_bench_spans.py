"""``bench/spans.py``: idle time named by the serving loop's spans, each
module's device time, and the ``mgnet_device_share`` reader, on
hand-built events and on a span stretch recorded here on the CPU."""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench import run, spans  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.tests.tiny import overrides  # noqa: E402

MS = 1e6        # ns


def _ev(name, start_ms, end_ms):
    return tr.Event(name, start_ms * MS, (end_ms - start_ms) * MS)


def _hand_trace(devices: dict) -> tr.Trace:
    return tr.Trace(devices=devices, host=[
        _ev(tr.WINDOW_SPAN, 0, 20),
        _ev("serve.call", 2, 18),
        _ev("serve.round", 3, 17),
        _ev("serve.ingest", 3, 5),
        _ev("serve.gate", 5, 9),
        _ev("PjitFunction(mgnet_score)", 6, 7),     # names nothing
        _ev("serve.route", 9, 11),
        _ev("serve.flush", 11, 15)])


OPS = [_ev("copy.1", 0, 1), _ev("fusion.2", 4, 4.5), _ev("mgnet.3", 7, 8),
       _ev("photonic_matmul.4", 12, 14), _ev("copy.5", 19, 20)]
# idle ms under each innermost span: 1..2 and 18..19 outside any call,
# 2..3 and 17..18 the call's own, 15..17 the round's own, 3..5 less
# 4..4.5 in ingest, 5..9 less 7..8 in the gate, 9..11 in route, 11..15
# less 12..14 in the flush; busy 5.5 ms
IDLE_MS = {spans.OUTSIDE: 2, "serve.call": 2, "serve.round": 2,
           "serve.ingest": 1.5, "serve.gate": 3, "serve.route": 2,
           "serve.flush": 2}


def test_idle_time_is_named_by_the_innermost_serve_span():
    red = spans.reduce(_hand_trace({0: OPS}))
    assert red.window_s == pytest.approx(0.020)
    assert red.busy_share == pytest.approx(5.5 / 20)
    assert red.idle_share == pytest.approx(
        {k: v / 20 for k, v in IDLE_MS.items()})
    assert red.total() == pytest.approx(1.0)
    # the acceptance sum: the named shares, the round's own and busy
    named = sum(red.idle_share[k] for k in (
        "serve.gate", "serve.ingest", "serve.flush", spans.OUTSIDE,
        "serve.round"))
    assert named + red.busy_share + sum(
        red.idle_share[k] for k in ("serve.call", "serve.route")) == \
        pytest.approx(1.0)
    # host self time of each span
    assert red.self_s["serve.call"] == pytest.approx(0.002)
    assert red.self_s["serve.round"] == pytest.approx(0.002)
    assert red.self_s["serve.gate"] == pytest.approx(0.004)
    assert "PjitFunction(mgnet_score)" not in red.self_s
    # longest holes of the first device, named at their middle
    assert red.gaps == [("serve.round", pytest.approx(0.005)),
                        ("serve.route", pytest.approx(0.004)),
                        ("serve.call", pytest.approx(0.003)),
                        ("serve.gate", pytest.approx(0.0025))]


def test_idle_shares_are_averaged_over_devices():
    red = spans.reduce(_hand_trace({0: OPS, 1: [_ev("fusion.1", 0, 20)]}))
    assert red.busy_share == pytest.approx((5.5 + 20) / 40)
    assert red.idle_share["serve.gate"] == pytest.approx(3 / 40)
    assert red.total() == pytest.approx(1.0)
    # one device only
    assert spans.reduce(_hand_trace({0: OPS, 1: []}), n_devices=1) \
        .busy_share == pytest.approx(5.5 / 20)


def test_a_trace_without_the_window_span_is_refused():
    t = _hand_trace({0: OPS})
    t.host = [e for e in t.host if e.name != tr.WINDOW_SPAN]
    with pytest.raises(ValueError, match="bench.window"):
        spans.reduce(t)


def test_module_time_is_the_union_of_its_intervals_over_devices():
    mods = {0: [_ev("jit_mgnet_score", 0, 2), _ev("jit_mgnet_score", 1, 3),
                _ev("jit_opto_encode", 3, 10)],
            1: [_ev("jit_mgnet_score", 0, 1)],
            2: [_ev("jit_mgnet_score", 0, 50)]}
    out = spans.module_seconds(mods, n_devices=2)
    assert out["jit_mgnet_score"] == pytest.approx(0.003 + 0.001)
    assert out["jit_opto_encode"] == pytest.approx(0.007)


def test_mgnet_device_share_reads_the_named_module(tmp_path, monkeypatch):
    """No such module (a program with unnamed jits): nothing, and no
    error."""
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    red = tr.Reduced(window_s=1.0, busy_s=0.5, kernel_s={}, gaps=[])
    assert run._reader("mgnet_device_share")(
        {"chips": 1, "trace": red}) is None


def _stretch(tmp_path):
    cell = "base224-keep33"
    c = run.load_cell(cell, overrides=overrides(cell))
    st = run.setup_cell(c, 5, require_tpu=False)
    monkey = run.TRACE_SLICE_S
    run.TRACE_SLICE_S = 1.0
    try:
        served = spans.span_slice(st["server"], st["streams"], c["traffic"],
                                  5, tmp_path)
    finally:
        run.TRACE_SLICE_S = monkey
    return st["server"], served


def test_recorded_span_stretch_on_the_cpu(tmp_path):
    """A CPU trace holds the spans' annotations (its host tracer is on for
    the CPU's operations), and the ring read onto its clock agrees."""
    server, served = _stretch(tmp_path)
    assert not server.spans.on and served.frames > 0
    red = spans.reduce(tr.load(tmp_path))
    assert red.total() == pytest.approx(1.0, abs=1e-9)
    assert {"serve.call", "serve.round", "serve.ingest", "serve.gate",
            "serve.route", "serve.flush", "serve.finish"} <= set(red.self_s)
    assert all(n.startswith(spans.PREFIX) or n == spans.OUTSIDE
               for n, _ in red.gaps)
    mods = spans.module_seconds(spans.load_modules(tmp_path))
    assert mods["jit_mgnet_score"] > 0 and mods["jit_opto_encode"] > 0
    assert {"jit_opto_embed", "jit_patch_order", "jit_gather_topk"} <= \
        set(mods)
    # the same spans from the ring alone, on the trace's clock, as on a TPU
    ring = spans.reduce(spans.ring_trace(list(server.spans.ring), tmp_path))
    assert ring.window_s == pytest.approx(red.window_s, rel=1e-3)
    assert ring.self_s.keys() == red.self_s.keys()
    assert ring.total() == pytest.approx(1.0, abs=1e-9)


def test_traced_run_reports_mgnet_device_share():
    cell = "base224-keep33"
    res = run.run_cell(cell, 9, 1.0, True, overrides=overrides(cell),
                       require_tpu=False)
    share = res["metrics"]["mgnet_device_share"]
    assert share["unit"] == "%" and 0 < share["value"] < 100
