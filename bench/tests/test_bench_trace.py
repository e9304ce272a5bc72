"""``bench/trace.py``: the busy union, kernel time and labelled idle gaps,
on hand-built events and on a trace recorded here on the CPU."""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from bench import trace as tr  # noqa: E402

MS = 1e6        # ns


def _ev(name, start_ms, dur_ms):
    return tr.Event(name, start_ms * MS, dur_ms * MS)


def test_overlapping_ops_count_once_and_gaps_are_named():
    t = tr.Trace(
        devices={0: [_ev("fused_ffn.3", 1, 4),        # 1..5
                     _ev("fusion.12", 2, 1),          # inside it
                     _ev("photonic_matmul.7", 4, 3),  # 4..7, overlaps
                     _ev("fused_ffn.9", 9, 1),        # 9..10
                     _ev("copy.1", 19, 3)]},          # clipped to 19..20
        host=[_ev(tr.WINDOW_SPAN, 0, 20),
              _ev("serve", 0, 20),
              _ev("numpy asarray", 7.5, 1),           # covers 7..9's middle
              _ev("sleep", 10, 9)])
    red = tr.reduce(t)
    assert red.window_s == pytest.approx(0.020)
    assert red.busy_s == pytest.approx(0.001 * (6 + 1 + 1))
    assert red.idle_share == pytest.approx(1 - 8 / 20)
    # self time: fused_ffn.3 less the fusion nested in it and the part of
    # photonic_matmul that overlaps it, plus fused_ffn.9
    assert red.kernel_s["fused_ffn"] == pytest.approx(0.002 + 0.001)
    assert red.kernel_s["fusion"] == pytest.approx(0.001)
    assert red.kernel_s["photonic_matmul"] == pytest.approx(0.003)
    assert red.kernel_s["copy"] == pytest.approx(0.001)      # clipped
    assert sum(red.kernel_s.values()) == pytest.approx(red.busy_s)
    # longest hole first: 10..19 under the sleep, then 0..1 and 7..9
    assert red.gaps[0] == ("sleep", pytest.approx(0.009))
    assert red.gaps[1][0] == "numpy asarray"
    assert red.gaps[1][1] == pytest.approx(0.002)
    assert red.gaps[2] == ("serve", pytest.approx(0.001))
    b = red.breakdown(top=2)
    assert sorted(n for n, _ in b["device_ops"]) == ["fused_ffn",
                                                    "photonic_matmul"]
    assert len(b["idle_gaps"]) == 2


def test_busy_is_averaged_over_devices_and_kernels_summed():
    t = tr.Trace(devices={0: [_ev("fused_ffn.1", 0, 10)],
                          1: [_ev("fused_ffn.1", 0, 4)]},
                 host=[_ev(tr.WINDOW_SPAN, 0, 10)])
    red = tr.reduce(t, n_devices=2)
    assert red.busy_s == pytest.approx(0.007)
    assert red.kernel_s["fused_ffn"] == pytest.approx(0.014)
    assert tr.reduce(t, n_devices=1).busy_s == pytest.approx(0.010)


def test_without_the_window_span_the_device_extent_is_the_window():
    t = tr.Trace(devices={0: [_ev("a.1", 2, 1), _ev("b.1", 5, 3)]})
    red = tr.reduce(t)
    assert red.window_s == pytest.approx(0.006)
    assert red.busy_s == pytest.approx(0.004)
    # no host event: a gap is named by the operation that ends it
    assert red.gaps[0] == ("before b", pytest.approx(0.002))
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce(tr.Trace(devices={0: []}, host=[_ev("serve", 0, 1)]))


def test_a_device_only_trace_takes_the_hosts_window():
    """Traced with the host tracer off, the window is the stretch's length
    on the host clock, never shorter than the device operations' span."""
    t = tr.Trace(devices={0: [_ev("a.1", 2, 1), _ev("b.1", 5, 3)]})
    red = tr.reduce(t, window_s=0.016)
    assert red.window_s == pytest.approx(0.016)
    assert red.busy_s == pytest.approx(0.004)
    assert red.idle_share == pytest.approx(0.75)
    assert tr.reduce(t, window_s=0.001).window_s == pytest.approx(0.006)
    # a host window span wins over the host clock's length
    t.host.append(_ev(tr.WINDOW_SPAN, 0, 10))
    assert tr.reduce(t, window_s=0.016).window_s == pytest.approx(0.010)


def test_base_name_reads_the_hlo_instruction_name():
    assert tr.base_name("flash_attention_masked.12") == \
        "flash_attention_masked"
    assert tr.base_name("fusion") == "fusion"
    assert tr.base_name("copy-start.3") == "copy-start"
    # how a TPU trace names its operations: the instruction's HLO text
    assert tr.base_name(
        "%photonic_matmul.19 = f32[512,768]{1,0:T(8,128)S(1)} custom-call("
        "s8[512,768]{1,0} %pad.60), custom_call_target=\"tpu_custom_call\""
    ) == "photonic_matmul"
    assert tr.base_name("%while.9 = (s32[], f32[4,99,768]) while(...)") \
        == "while"


def test_self_time_of_a_loop_excludes_its_body():
    t = tr.Trace(devices={0: [_ev("%while.1 = () while()", 0, 10),
                              _ev("%fused_ffn.2 = f32[] custom-call()", 1, 3),
                              _ev("%fused_ffn.2 = f32[] custom-call()", 5, 3)]},
                 host=[_ev(tr.WINDOW_SPAN, 0, 10)])
    red = tr.reduce(t)
    assert red.kernel_s == {"while": pytest.approx(0.004),
                            "fused_ffn": pytest.approx(0.006)}


def test_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(5):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = tr.reduce(tr.load(tmp_path))
    assert 0 < red.busy_s <= red.window_s
    assert 0.0 <= red.idle_share < 1.0
    assert sum(red.kernel_s.values()) >= red.busy_s * (1 - 1e-9)
    assert all(isinstance(n, str) and s > 0 for n, s in red.gaps)
