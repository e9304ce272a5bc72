"""Serving subsystem tests: bucket ladder, temporal mask cache, micro-batch
scheduler, stream accounting, VideoStream determinism, and the engine end to
end (incl. the Pallas serving path in interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import smoke_variant
from repro.configs.opto_vit import get_config
from repro.core.energy import EnergyReport, aggregate_reports
from repro.data.pipeline import VideoStream, prefetch_to_device
from repro.serving.accounting import StreamAccounting
from repro.serving.buckets import BucketHistogram, BucketLadder
from repro.serving.engine import ServingConfig, ServingEngine
from repro.serving.mask_cache import GateOps, TemporalMaskCache
from repro.serving.scheduler import MicroBatcher


# --------------------------------------------------------------------------
# bucket ladder
# --------------------------------------------------------------------------

def test_ladder_from_fractions():
    lad = BucketLadder.from_fractions(36, (0.25, 0.5, 0.75, 1.0))
    assert lad.sizes == (9, 18, 27, 36)
    assert lad.cap == 36


def test_ladder_routes_to_smallest_covering_bucket():
    lad = BucketLadder((9, 18, 27, 36))
    assert lad.route(0) == 9
    assert lad.route(9) == 9
    assert lad.route(10) == 18
    assert lad.route(28) == 36
    assert lad.route(99) == 36          # over-budget clips to the cap
    np.testing.assert_array_equal(
        lad.route_many([0, 9, 10, 28, 99]), [9, 9, 18, 36, 36])


def test_ladder_rejects_bad_shapes():
    with pytest.raises(ValueError):
        BucketLadder(())
    with pytest.raises(ValueError):
        BucketLadder((9, 9, 18))
    with pytest.raises(ValueError):
        BucketLadder((18, 9))


def test_ladder_budget_edges():
    """Budget 0 (MGNet found nothing — still encode the smallest bucket,
    the [cls] path needs tokens) and budget == N (dense fallback, no
    over-routing past the cap)."""
    n = 36
    lad = BucketLadder.from_fractions(n, (0.25, 0.5, 0.75, 1.0))
    assert lad.route(0) == lad.sizes[0]
    assert lad.route(n) == n == lad.cap
    np.testing.assert_array_equal(lad.route_many([0, n]), [lad.sizes[0], n])
    # a single-bucket ladder sends every budget to that bucket
    one = BucketLadder((n,))
    assert one.route(0) == one.route(n) == one.route(n + 99) == n
    # fractions below 1/N clamp to one patch, never zero
    tiny = BucketLadder.from_fractions(n, (0.001, 1.0))
    assert tiny.sizes[0] == 1


def test_histogram_counts():
    lad = BucketLadder((4, 8))
    h = BucketHistogram(lad)
    h.add(4)
    h.add(8, 3)
    assert h.as_dict() == {4: 1, 8: 3}
    assert h.total == 4


# --------------------------------------------------------------------------
# temporal mask cache
# --------------------------------------------------------------------------

def _static_frames(n, h=8, val=0.0):
    return np.full((n, h, h, 3), val, np.float32)


def test_mask_cache_reuses_on_static_scene():
    cache = TemporalMaskCache(refresh=100, delta_threshold=0.5)
    calls = []

    def score_fn(f):
        calls.append(f.shape[0])
        return np.zeros((f.shape[0], 4), np.float32)

    scores, n = cache.gate(_static_frames(6), np.arange(6), score_fn)
    assert scores.shape == (6, 4)
    assert n == 1                        # only the very first frame scored
    assert cache.reused_frames == 5
    # identical follow-up chunk: full reuse, no scoring call at all
    _, n2 = cache.gate(_static_frames(6), np.arange(6, 12), score_fn)
    assert n2 == 0
    assert cache.reuse_rate == pytest.approx(11 / 12)


def test_mask_cache_refresh_period_bounds_staleness():
    cache = TemporalMaskCache(refresh=4, delta_threshold=1e9)
    scored = []

    def score_fn(f):
        scored.append(f.shape[0])
        return np.zeros((f.shape[0], 4), np.float32)

    _, n = cache.gate(_static_frames(8), np.arange(8), score_fn)
    assert n == 2                        # frames 0 and 4 (every 4th)


def test_mask_cache_delta_trigger_fires_on_scene_change():
    cache = TemporalMaskCache(refresh=1000, delta_threshold=0.3)

    def score_fn(f):
        # score = per-frame mean brightness, so the output tells us which
        # frame each returned score row came from
        per_frame = f.mean(axis=(1, 2, 3)).astype(np.float32)
        return np.repeat(per_frame[:, None], 4, axis=1)

    frames = _static_frames(6)
    frames[3:] = 1.0                     # scene cut at frame 3
    scores, n = cache.gate(frames, np.arange(6), score_fn)
    assert n == 2                        # frame 0 + the cut frame
    assert scores[2].mean() == pytest.approx(0.0)
    assert scores[3].mean() == pytest.approx(1.0)
    assert scores[5].mean() == pytest.approx(1.0)   # reused post-cut mask


def test_mask_cache_refresh_boundary_is_inclusive():
    """idx - ref_idx == refresh must re-score (staleness bound is >=, not
    >): with refresh=4 a frame exactly 4 after the reference is scored."""
    def score_fn(f):
        return np.zeros((f.shape[0], 4), np.float32)

    # frames 0..4 in one chunk: 0 scores (cold), 4 is exactly refresh away
    cache = TemporalMaskCache(refresh=4, delta_threshold=1e9)
    _, n = cache.gate(_static_frames(5), np.arange(5), score_fn)
    assert n == 2                            # frames 0 and 4, not 3
    # one frame short of the boundary: only the cold score
    short = TemporalMaskCache(refresh=4, delta_threshold=1e9)
    _, n2 = short.gate(_static_frames(4), np.arange(4), score_fn)
    assert n2 == 1


def test_mask_cache_delta_exactly_at_threshold_reuses():
    """The delta trigger is strict (delta > threshold): a frame whose mean
    abs delta equals the threshold exactly reuses the cached mask."""
    from repro.core.mgnet import frame_delta
    thr_frames = _static_frames(2)
    thr_frames[1] = 0.25                      # uniform delta of exactly 0.25
    delta = float(frame_delta(thr_frames[1:2], thr_frames[0])[0])
    assert delta == pytest.approx(0.25)

    def score_fn(f):
        return np.zeros((f.shape[0], 4), np.float32)

    at = TemporalMaskCache(refresh=1000, delta_threshold=delta)
    _, n_at = at.gate(thr_frames, np.arange(2), score_fn)
    assert n_at == 1                          # == threshold -> reuse
    below = TemporalMaskCache(refresh=1000,
                              delta_threshold=delta - 1e-6)
    _, n_below = below.gate(thr_frames, np.arange(2), score_fn)
    assert n_below == 2                       # just past it -> re-score


def test_mask_cache_static_score_shape():
    """score_fn must always see the full chunk shape (jit-retrace guard)."""
    cache = TemporalMaskCache(refresh=4, delta_threshold=1e9)
    shapes = set()

    def score_fn(f):
        shapes.add(f.shape)
        return np.zeros((f.shape[0], 4), np.float32)

    for c in range(4):
        cache.gate(_static_frames(8), np.arange(8 * c, 8 * c + 8), score_fn)
    assert shapes == {(8, 8, 8, 3)}


_OPS = GateOps.build()


@jax.jit
def _jit_score(f):
    # a stand-in MGNet: per-frame rows that depend on every pixel
    flat = f.reshape(f.shape[0], -1)
    return jnp.tanh(flat[:, :4] * 3.0 + flat.mean(axis=1, keepdims=True))


def _chunks(kind, n_chunks=4, c=8, h=8, seed=0):
    """(frames, frame_idx, eligible) chunks of one stream: ``drift`` moves
    a little every frame, ``cut`` adds a scene cut inside chunk 1, ``tail``
    leaves the last chunk's final frames ineligible."""
    rng = np.random.default_rng(seed)
    base = rng.random((h, h, 3)).astype(np.float32)
    step = 0.05 if kind == "drift" else 0.01
    out = []
    for k in range(n_chunks):
        f = np.stack([base + step * (k * c + i) for i in range(c)]
                     ).astype(np.float32)
        if kind == "cut" and k == 1:
            f[3:] += 2.0
        idx = np.arange(k * c, (k + 1) * c)
        elig = None
        if kind == "tail" and k == n_chunks - 1:
            elig = idx < k * c + 5
        out.append((f, idx, elig))
    return out


@pytest.mark.parametrize("kind,refresh,thr", [
    ("drift", 4, 1e9),       # the staleness bound alone
    ("drift", 1000, 0.12),   # the delta trigger alone
    ("cut", 16, 0.5),        # a scene cut, and chunks scoring nothing
    ("tail", 3, 0.3),        # ineligible tail frames
])
def test_mask_cache_device_gate_matches_host_bitwise(kind, refresh, thr):
    """MGNet on the device view, scores kept there: each frame's scores,
    n_scored and the reference state are bitwise the host path's."""
    host = TemporalMaskCache(refresh, thr)
    dev = TemporalMaskCache(refresh, thr)
    reused = 0
    for f, idx, elig in _chunks(kind):
        sh, nh = host.gate(f, idx, _jit_score, eligible=elig)
        sd, nd = dev.gate(f, idx, _jit_score, eligible=elig,
                          device_frames=jnp.asarray(f), ops=_OPS)
        assert isinstance(sh, np.ndarray) and isinstance(sd, jax.Array)
        assert nd == nh
        reused += nh == 0
        np.testing.assert_array_equal(np.asarray(sd), sh)
        assert sd.dtype == sh.dtype == np.float32
        assert isinstance(dev._ref_scores, jax.Array)
        hs, ds = host.state_dict(), dev.state_dict()
        assert hs.keys() == ds.keys()
        for k in hs:
            np.testing.assert_array_equal(ds[k], hs[k])
    assert (dev.scored_frames, dev.reused_frames) == \
        (host.scored_frames, host.reused_frames)
    if kind == "cut":
        assert reused                     # a chunk that scored nothing


def test_mask_cache_host_scores_pull_only_scored_chunks():
    """``host_scores`` crosses for a scored chunk and reuses the kept host
    row for one that scored nothing — and is what the gate selected."""
    cache = TemporalMaskCache(refresh=16, delta_threshold=1e9)
    pulls = []
    for f, idx, _ in _chunks("drift"):
        sd, n = cache.gate(f, idx, _jit_score, device_frames=jnp.asarray(f),
                           ops=_OPS)
        sh, pulled = cache.host_scores(sd, n)
        pulls.append(pulled)
        np.testing.assert_array_equal(sh, np.asarray(sd))
    assert pulls == [True, False, True, False]


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.uint8])
def test_mask_cache_delta_buffer_is_frame_delta_bitwise(dtype):
    from repro.core.mgnet import frame_delta
    rng = np.random.default_rng(3)
    cache = TemporalMaskCache()
    for _ in range(6):
        a = (rng.random((32, 32, 3)) * 4).astype(dtype)
        b = (rng.random((32, 32, 3)) * 4).astype(dtype)
        assert cache._delta(a, b) == float(frame_delta(a[None], b)[0])


@pytest.mark.parametrize("on_device", [False, True])
def test_mask_cache_walk_allocates_no_frame_temporaries(on_device):
    """A warm gate call over 224 px frames allocates less than two frames:
    the deltas go through one reused buffer, MGNet's host input through
    another, and the device path builds none."""
    import tracemalloc
    rng = np.random.default_rng(4)
    f = rng.random((8, 224, 224, 3)).astype(np.float32)
    dev = jnp.asarray(f) if on_device else None
    cache = TemporalMaskCache(refresh=4, delta_threshold=1e9)

    def score(x):
        return (_jit_score(x) if on_device
                else np.zeros((x.shape[0], 4), np.float32))

    for k in range(2):                    # cold, then warm: every program
        cache.gate(f, np.arange(8 * k, 8 * k + 8), score,
                   device_frames=dev, ops=_OPS)
    tracemalloc.start()
    try:
        _, n = cache.gate(f, np.arange(16, 24), score, device_frames=dev,
                          ops=_OPS)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert n == 2                         # frames 16 and 20 re-scored
    assert peak < 2 * f[0].nbytes, peak


# --------------------------------------------------------------------------
# micro-batch scheduler
# --------------------------------------------------------------------------

def test_microbatcher_flushes_at_capacity():
    mb = MicroBatcher(microbatch=4)
    toks = jnp.arange(4 * 2 * 3, dtype=jnp.float32).reshape(4, 2, 3)
    assert mb.push_many(8, toks[:3], [0, 1, 2]) == []
    assert mb.pending == 3
    out = mb.push_many(8, toks[3:], [3])
    assert len(out) == 1
    fb = out[0]
    assert fb.bucket == 8 and fb.n_real == 4 and fb.frame_idx == [0, 1, 2, 3]
    np.testing.assert_array_equal(np.asarray(fb.tokens), np.asarray(toks))
    assert mb.pending == 0


def test_microbatcher_splits_oversized_groups():
    mb = MicroBatcher(microbatch=2)
    toks = jnp.arange(5 * 1 * 1, dtype=jnp.float32).reshape(5, 1, 1)
    out = mb.push_many(4, toks, [0, 1, 2, 3, 4])
    assert [f.frame_idx for f in out] == [[0, 1], [2, 3]]
    assert mb.pending == 1
    (tail,) = mb.drain()
    assert tail.frame_idx == [4] and tail.n_real == 1
    assert tail.tokens.shape == (2, 1, 1)            # zero-padded to mb
    assert float(tail.tokens[1].sum()) == 0.0


def test_microbatcher_keeps_buckets_separate():
    mb = MicroBatcher(microbatch=2)
    a = jnp.ones((1, 2, 2))
    b = jnp.ones((1, 4, 2))
    assert mb.push(2, a[0], 0) == []
    assert mb.push(4, b[0], 1) == []
    out = mb.push(2, a[0], 2)
    assert len(out) == 1 and out[0].bucket == 2
    assert mb.pending == 1               # bucket-4 frame still queued


# --------------------------------------------------------------------------
# accounting
# --------------------------------------------------------------------------

def test_energy_report_aggregation():
    a = EnergyReport(adc_uj=1.0, optical_us=2.0)
    b = EnergyReport(adc_uj=3.0, dac_uj=1.0)
    s = aggregate_reports([a, b])
    assert s.adc_uj == pytest.approx(4.0)
    assert s.dac_uj == pytest.approx(1.0)
    assert s.optical_us == pytest.approx(2.0)
    half = s.scaled(0.5)
    assert half.adc_uj == pytest.approx(2.0)
    a += b
    assert a.adc_uj == pytest.approx(4.0)


def test_stream_accounting_empty_flushes():
    """Zero-frame flushes (fully-padded micro-batches, idle streams) must
    not perturb the aggregate: no frames, no energy, KFPS/W stays 0 and
    the mean-frame report divides by nothing."""
    cfg = get_config("tiny", img_size=96, mgnet=True)
    acct = StreamAccounting(cfg)
    acct.add_encode(18, 0)
    acct.add_mgnet(0)
    assert acct.frames == 0 and acct.scored_frames == 0
    assert acct.kfps_per_watt == 0.0
    assert acct.mean_frame.total_uj == 0.0
    assert acct.total.total_uj == pytest.approx(0.0)
    # real frames after empty flushes aggregate exactly as if alone
    acct.add_encode(18, 3)
    fresh = StreamAccounting(cfg)
    fresh.add_encode(18, 3)
    assert acct.frames == fresh.frames == 3
    assert acct.mean_frame.total_uj == pytest.approx(
        fresh.mean_frame.total_uj)
    assert acct.kfps_per_watt == pytest.approx(fresh.kfps_per_watt)


def test_accounting_summary_reports_hits_and_launches():
    cfg = get_config("tiny", img_size=96, mgnet=True)
    acct = StreamAccounting(cfg, ladder_sizes=(9, 18, 27, 36))
    acct.add_encode(18, 4)
    acct.add_encode(18, 2)
    acct.add_encode(27, 4)
    with pytest.warns(UserWarning, match="dead ladder buckets"):
        s = acct.summary()
    assert "k=18: 6 hits/2 launches" in s
    assert "k=27: 4 hits/1 launches" in s
    assert "k=9: 0 hits/0 launches" in s
    assert "[dead: k=9, k=36]" in s
    assert acct.dead_buckets() == (9, 36)


def test_accounting_summary_no_dead_buckets_no_warning():
    import warnings as _w
    cfg = get_config("tiny", img_size=96, mgnet=True)
    acct = StreamAccounting(cfg, ladder_sizes=(9, 18))
    acct.add_encode(9, 1)
    acct.add_encode(18, 1)
    with _w.catch_warnings():
        _w.simplefilter("error")             # any warning -> test failure
        s = acct.summary()
    assert acct.dead_buckets() == ()
    assert "dead" not in s


def test_accounting_summary_without_ladder():
    """No registered ladder (the dense driver): summary reports whatever
    buckets were hit and never warns — a dense run has no ladder to tune."""
    import warnings as _w
    cfg = get_config("tiny", img_size=96, mgnet=True)
    acct = StreamAccounting(cfg)
    acct.add_encode(36, 5)
    with _w.catch_warnings():
        _w.simplefilter("error")
        s = acct.summary()
    assert "k=36: 5 hits/1 launches" in s


def test_stream_accounting_tracks_buckets_and_mgnet():
    cfg = get_config("tiny", img_size=96, mgnet=True)
    acct = StreamAccounting(cfg)
    acct.add_encode(18, 4)
    acct.add_mgnet(2)
    assert acct.frames == 4 and acct.scored_frames == 2
    e_small = acct.mean_frame.total_uj
    dense = StreamAccounting(cfg)
    dense.add_encode(36, 4)
    dense.add_mgnet(2)
    # fewer kept patches -> strictly less energy -> more KFPS/W
    assert e_small < dense.mean_frame.total_uj
    assert acct.kfps_per_watt > dense.kfps_per_watt
    # a gated stream must beat its own dense baseline
    assert acct.kfps_per_watt > acct.dense_baseline_kfps_per_watt()


# --------------------------------------------------------------------------
# video stream
# --------------------------------------------------------------------------

def test_video_stream_deterministic_and_coherent():
    vs = VideoStream(img_size=32, patch=8, seed=0, cut_every=8)
    a = vs.frames_at(0, 12)
    b = vs.frames_at(4, 4)
    np.testing.assert_array_equal(np.asarray(a["frames"][4:8]),
                                  np.asarray(b["frames"]))
    assert a["patch_mask"].shape == (12, 16)
    assert float(a["patch_mask"].sum(-1).min()) >= 1.0   # box always visible
    # consecutive frames are closer than frames across a scene cut
    f = np.asarray(a["frames"])
    d_in = np.abs(f[1] - f[0]).mean()
    d_cut = np.abs(f[8] - f[7]).mean()
    assert d_in < d_cut


def test_prefetch_preserves_order():
    vs = VideoStream(img_size=16, patch=8, seed=1)
    it = prefetch_to_device(vs.chunks(2), depth=3)
    seen = [int(next(it)["frame_idx"][0]) for _ in range(4)]
    assert seen == [0, 2, 4, 6]


# --------------------------------------------------------------------------
# engine end to end
# --------------------------------------------------------------------------

def _smoke_engine(backend: str, attn_backend: str = "",
                  ffn_backend: str = "", **serve_kw) -> ServingEngine:
    cfg = smoke_variant(get_config("tiny")).with_(
        mgnet=True, mgnet_embed=32, mgnet_heads=2, matmul_backend=backend,
        attn_backend=attn_backend, ffn_backend=ffn_backend)
    sc = ServingConfig(microbatch=4, chunk=8, mask_refresh=8, **serve_kw)
    return ServingEngine(cfg, sc, n_classes=8, seed=0)


def test_engine_streams_end_to_end():
    eng = _smoke_engine("photonic_sim")
    stream = VideoStream(img_size=32, patch=8, cut_every=16)
    res = eng.run(stream, n_frames=32)
    assert res.frames >= 32
    assert sorted(res.predictions) == list(range(res.frames))
    assert sum(res.bucket_hits.values()) == res.frames
    assert 0 < res.scored_frames < res.frames        # mask reuse happened
    assert res.kfps_per_watt > 0 and res.mean_frame_uj > 0
    assert res.fps > 0


def test_engine_is_deterministic_across_runs():
    stream = VideoStream(img_size=32, patch=8, cut_every=16)
    r1 = _smoke_engine("photonic_sim").run(stream, n_frames=24)
    r2 = _smoke_engine("photonic_sim").run(stream, n_frames=24)
    assert r1.predictions == r2.predictions
    assert r1.bucket_hits == r2.bucket_hits
    assert r1.scored_frames == r2.scored_frames


def test_engine_pallas_serving_path():
    """The acceptance path: streaming on the int8 Pallas kernel backend."""
    eng = _smoke_engine("photonic_pallas")
    stream = VideoStream(img_size=32, patch=8, cut_every=16)
    res = eng.run(stream, n_frames=16)
    assert res.frames >= 16
    assert sorted(res.predictions) == list(range(res.frames))


def test_engine_fused_flash_serving_path():
    """The tentpole path: int8 Pallas matmul backend + fused RoI-masked
    flash attention core, streaming end to end — predicting (nearly) the
    same classes as the xla attention core. The two dataflows agree only
    to reassociation noise, so a near-tied frame may legitimately flip:
    require >= 90% class agreement, not bitwise equality."""
    stream = VideoStream(img_size=32, patch=8, cut_every=16)
    res_f = _smoke_engine("photonic_pallas", attn_backend="flash").run(
        stream, n_frames=16)
    assert res_f.frames >= 16
    assert sorted(res_f.predictions) == list(range(res_f.frames))
    res_x = _smoke_engine("photonic_pallas").run(stream, n_frames=16)
    agree = sum(res_f.predictions[i] == res_x.predictions[i]
                for i in res_f.predictions) / len(res_f.predictions)
    assert agree >= 0.9, (agree, res_f.predictions, res_x.predictions)


def test_engine_fully_fused_serving_path():
    """The PR's tentpole path: int8 Pallas matmuls + fused flash attention
    + fused FFN, the whole encoder one cached jit. Bucketed encodes carry
    no kv_len, so the fused FFN is bit-identical to the composed dispatch
    — predictions must match the composed engine exactly."""
    stream = VideoStream(img_size=32, patch=8, cut_every=16)
    res_f = _smoke_engine("photonic_pallas", attn_backend="flash",
                          ffn_backend="fused").run(stream, n_frames=16)
    assert res_f.frames >= 16
    assert sorted(res_f.predictions) == list(range(res_f.frames))
    res_c = _smoke_engine("photonic_pallas", attn_backend="flash").run(
        stream, n_frames=16)
    assert res_f.predictions == res_c.predictions
    assert res_f.bucket_hits == res_c.bucket_hits
    # per-bucket launch telemetry rides along in the result
    assert sum(res_f.bucket_launches.values()) > 0
    assert set(res_f.bucket_launches) <= set(res_f.bucket_hits)


def test_engine_one_shape_fused_ffn_path():
    """One-shape mode on the fully-fused stack: the static per-bucket
    kv_len prunes FFN rows too (the packed skip), which legitimately
    changes w8a8 activation scale sets — class agreement >= 90%, same
    contract as the other cross-dataflow engine comparisons."""
    stream = VideoStream(img_size=32, patch=8, cut_every=16)
    res_o = _smoke_engine("photonic_pallas", attn_backend="flash",
                          ffn_backend="fused", one_shape=True).run(
        stream, n_frames=16)
    assert res_o.frames >= 16
    assert sorted(res_o.predictions) == list(range(res_o.frames))
    res_g = _smoke_engine("photonic_pallas", attn_backend="flash",
                          ffn_backend="fused").run(stream, n_frames=16)
    agree = sum(res_o.predictions[i] == res_g.predictions[i]
                for i in res_g.predictions) / len(res_g.predictions)
    assert agree >= 0.9, (agree, res_o.predictions, res_g.predictions)
    assert res_o.mean_frame_uj == pytest.approx(res_g.mean_frame_uj)


def test_engine_one_shape_mode_matches_bucketed():
    """Fixed-sensor-buffer (one-shape) serving: every encode at the ladder
    cap with a static packed kept-count. Gating stats and bucket routing
    are identical to the gathered mode; predictions agree to the
    masked-vs-gathered parity contract (>= 90% on a float backend)."""
    stream = VideoStream(img_size=32, patch=8, cut_every=16)
    res_g = _smoke_engine("bf16").run(stream, n_frames=16)
    res_o = _smoke_engine("bf16", one_shape=True).run(stream, n_frames=16)
    assert res_o.frames == res_g.frames
    assert res_o.bucket_hits == res_g.bucket_hits
    assert res_o.scored_frames == res_g.scored_frames
    assert sorted(res_o.predictions) == list(range(res_o.frames))
    agree = sum(res_o.predictions[i] == res_g.predictions[i]
                for i in res_g.predictions) / len(res_g.predictions)
    assert agree >= 0.9, (agree, res_o.predictions, res_g.predictions)
    # accelerator-model energy is identical: the packed prefix lets the
    # static schedule stream only the k live rows, exactly like a gather
    # (the cap-size host FFN is a functional-sim artifact)
    assert res_o.mean_frame_uj == pytest.approx(res_g.mean_frame_uj)
    assert res_o.kfps_per_watt == pytest.approx(res_g.kfps_per_watt)


def test_engine_force_bucket_pins_routing():
    eng = _smoke_engine("bf16", force_bucket=0.5)
    stream = VideoStream(img_size=32, patch=8, cut_every=16)
    res = eng.run(stream, n_frames=16)
    n = eng.n_patches
    pinned = eng.ladder.route(n // 2)
    assert res.bucket_hits[pinned] == res.frames
    assert all(v == 0 for k, v in res.bucket_hits.items() if k != pinned)


def test_engine_dense_baseline_covers_stream():
    """The mask-mode dense path serves the same frames with the same gating
    stats, at strictly higher modeled energy per frame (compute not
    reduced). Logit-level agreement between the two paths is the bucketed-
    pruning parity contract — tests/test_bucket_parity.py."""
    eng = _smoke_engine("bf16")
    stream = VideoStream(img_size=32, patch=8, cut_every=16)
    res_b = eng.run(stream, n_frames=16)
    res_d = eng.run_dense(stream, n_frames=16)
    assert res_b.frames == res_d.frames
    assert sorted(res_b.predictions) == sorted(res_d.predictions)  # coverage
    assert res_b.scored_frames == res_d.scored_frames  # identical gating
    assert res_b.mean_frame_uj < res_d.mean_frame_uj


def test_engine_serves_exact_frame_count():
    """n_frames that is not a chunk multiple: trailing frames of the last
    ingest chunk are gated but never routed, encoded or accounted."""
    eng = _smoke_engine("bf16")
    stream = VideoStream(img_size=32, patch=8, cut_every=16)
    res = eng.run(stream, n_frames=13)          # chunk=8 -> partial tail
    assert res.frames == 13
    assert sorted(res.predictions) == list(range(13))
    assert sum(res.bucket_hits.values()) == 13
    d = eng.run_dense(stream, n_frames=13)
    assert d.frames == 13
    assert sorted(d.predictions) == list(range(13))
    # trailing frames of the last chunk must not be scored or accounted:
    # a 1-frame run can have scored at most that one frame
    one = _smoke_engine("bf16").run(stream, n_frames=1)
    assert one.frames == 1
    assert one.scored_frames == 1 and one.reused_frames == 0


def test_engine_gather_matches_select_topk():
    """The engine's shared-order gather must select exactly what the public
    select_topk_patches API selects, for every ladder bucket."""
    from repro.core.mgnet import select_topk_patches
    from repro.serving.engine import _gather_topk_rows
    scores = jax.random.normal(jax.random.PRNGKey(0), (3, 16))
    scores = scores.at[:, 7].set(scores[:, 2])       # exact tie
    toks = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 5))
    order = jnp.argsort(scores, axis=-1, stable=True, descending=True)
    for k in (4, 8, 12, 16):
        via_engine = _gather_topk_rows(toks, order, k)
        via_api, _ = select_topk_patches(scores, toks, k)
        np.testing.assert_array_equal(np.asarray(via_engine),
                                      np.asarray(via_api))


def test_engine_requires_mgnet():
    cfg = smoke_variant(get_config("tiny"))          # mgnet=False
    with pytest.raises(ValueError):
        ServingEngine(cfg, ServingConfig())
