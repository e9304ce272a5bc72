"""Fault-tolerant serving tests: injector determinism + inertness, retry
transparency, per-session quarantine, ServeError attribution with partial
results, checkpoint/restore + migration bitwise round-trips, checkpoint
I/O fault tolerance, load shedding, the flush watchdog, and
``serve_with_restarts`` crash recovery."""

import warnings

import numpy as np
import pytest

from repro.core.noise import NoiseSpec
from repro.data.pipeline import video_fleet
from repro.serving.engine import _smoke_cfg
from repro.serving.faults import (FatalFault, FaultInjector, FaultSpec,
                                  ServeError, TransientFault,
                                  serve_with_restarts)
from repro.serving.server import ServerConfig, StreamServer

N_FRAMES = 24


def _server(cfg, **kw):
    base = dict(warm_start=False, mesh="off", chunk=8, microbatch=4)
    base.update(kw)
    return StreamServer(cfg, ServerConfig(**base))


def _serve(srv, streams, n_frames=N_FRAMES):
    for st in streams:
        srv.add_session(st, n_frames=n_frames)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return srv.serve()


def _preds(res, n=N_FRAMES):
    return np.array([res.predictions[i] for i in range(n)])


@pytest.fixture(scope="module")
def base3():
    """Default-backend baseline: 3 streams x N_FRAMES, fault-free."""
    cfg = _smoke_cfg("")
    streams = video_fleet(3, img_size=cfg.img_size, patch=cfg.patch)
    res = _serve(_server(cfg), streams)
    return cfg, streams, {sid: _preds(r) for sid, r in res.items()}, res


# --------------------------------------------------------------------------
# injector: determinism, replayability, transient clearing
# --------------------------------------------------------------------------

def test_injector_deterministic_and_order_independent():
    """Fault decisions are a pure function of (seed, site) — two injectors
    agree site-by-site, and probing sites in a different order changes
    nothing (no shared RNG stream to desynchronize)."""
    spec = FaultSpec(flush_fault_rate=0.3, ingest_fault_rate=0.2, seed=42)
    sites = [(k, (sid, f)) for k in (8, 16) for sid in (0, 1)
             for f in range(10)]
    a, b = FaultInjector(spec), FaultInjector(spec)
    hits_a = [a._hit(spec.flush_fault_rate, "flush", k, *t)
              for k, t in sites]
    hits_b = [b._hit(spec.flush_fault_rate, "flush", k, *t)
              for k, t in reversed(sites)]
    assert hits_a == list(reversed(hits_b))
    assert any(hits_a) and not all(hits_a)
    # a different seed draws a different fault pattern
    c = FaultInjector(FaultSpec(flush_fault_rate=0.3, seed=43))
    hits_c = [c._hit(spec.flush_fault_rate, "flush", k, *t)
              for k, t in sites]
    assert hits_a != hits_c


def test_injector_transient_site_clears_after_n_failures():
    """A transient site fails exactly its first ``transient_failures``
    attempts, then succeeds — the retry loop always converges."""
    inj = FaultInjector(FaultSpec(flush_fault_rate=1.0,
                                  transient_failures=2, seed=0))
    for attempt in (0, 1):
        with pytest.raises(TransientFault):
            inj.flush(8, (0, 0), attempt=attempt)
    inj.flush(8, (0, 0), attempt=2)            # cleared
    assert inj.injected["flush_transient"] == 2


def test_injector_hard_fail_targets_one_session():
    inj = FaultInjector(FaultSpec(hard_fail_session=1,
                                  hard_fail_at_chunk=2))
    inj.ingest(0, 2)
    inj.ingest(1, 1)
    with pytest.raises(FatalFault, match="session 1"):
        inj.ingest(1, 2)


# --------------------------------------------------------------------------
# hygiene: no FaultSpec -> no injector, zero-rate spec -> bitwise identical
# --------------------------------------------------------------------------

def test_no_faultspec_means_no_injector(base3):
    cfg, _, _, _ = base3
    srv = _server(cfg)
    assert srv.faults is None and srv._injector is None
    assert srv._watchdog is False and srv.telemetry is None


@pytest.mark.parametrize("backend,attn,ffn", [
    ("bf16", "", ""),
    ("photonic_pallas", "", ""),
])
def test_fault_layer_inert_without_faults(backend, attn, ffn):
    _inertness_case(backend, attn, ffn)


@pytest.mark.slow
@pytest.mark.parametrize("backend,attn,ffn", [
    ("photonic_sim", "", ""),
    ("photonic_pallas", "flash", "fused"),    # the acceptance path
])
def test_fault_layer_inert_without_faults_slow(backend, attn, ffn):
    _inertness_case(backend, attn, ffn)


def _inertness_case(backend, attn, ffn):
    """A zero-rate FaultSpec (injector present, never fires) must serve
    bitwise identically to no spec at all: the fault layer adds no RNG
    draws and no dispatch changes to the hot path."""
    cfg = _smoke_cfg(backend, attn, ffn)
    streams = video_fleet(2, img_size=cfg.img_size, patch=cfg.patch)
    plain = _serve(_server(cfg), streams, n_frames=12)
    spec = FaultSpec(seed=9)                  # all rates zero
    armed = _serve(_server(cfg, faults=spec), streams, n_frames=12)
    for sid in plain:
        np.testing.assert_array_equal(_preds(plain[sid], 12),
                                      _preds(armed[sid], 12))
        assert not armed[sid].poisoned and armed[sid].retries == 0


# --------------------------------------------------------------------------
# retry transparency + quarantine isolation
# --------------------------------------------------------------------------

def test_transient_flush_faults_bitwise_transparent(base3):
    cfg, streams, bp, _ = base3
    srv = _server(cfg, faults=FaultSpec(flush_fault_rate=0.3, seed=7))
    res = _serve(srv, streams)
    assert sum(r.retries for r in res.values()) > 0
    for sid in bp:
        assert not res[sid].poisoned
        np.testing.assert_array_equal(_preds(res[sid]), bp[sid])


def test_ingest_faults_retry_without_losing_chunks(base3):
    cfg, streams, bp, _ = base3
    srv = _server(cfg, faults=FaultSpec(ingest_fault_rate=0.3, seed=11))
    res = _serve(srv, streams)
    assert sum(r.retries for r in res.values()) > 0
    for sid in bp:
        assert res[sid].frames == N_FRAMES
        np.testing.assert_array_equal(_preds(res[sid]), bp[sid])


def test_hard_failed_session_is_quarantined_others_bitwise(base3):
    """Gate B shape: the victim comes back poisoned, the survivors are
    bitwise identical to a run where the victim was never registered."""
    cfg, streams, bp, _ = base3
    srv = _server(cfg, faults=FaultSpec(hard_fail_session=1,
                                        hard_fail_at_chunk=1, seed=1))
    with pytest.warns(UserWarning, match="quarantined session"):
        for st in streams:
            srv.add_session(st, n_frames=N_FRAMES)
        res = srv.serve()
    assert res[1].poisoned and "session 1" in res[1].failure
    assert res[1].frames < N_FRAMES            # partial, not silently full
    for sid in (0, 2):
        assert not res[sid].poisoned
        np.testing.assert_array_equal(_preds(res[sid]), bp[sid])
    # never-registered counterfactual (sids remap by registration order)
    ref = _serve(_server(cfg), [streams[0], streams[2]])
    np.testing.assert_array_equal(_preds(ref[0]), bp[0])
    np.testing.assert_array_equal(_preds(ref[1]), bp[2])


def test_retry_exhaustion_fails_only_owning_session(base3):
    """A permanently-failing flush site (more consecutive failures than
    the retry limit) quarantines its owner; co-tenants still finish."""
    cfg, streams, bp, _ = base3
    spec = FaultSpec(flush_fault_rate=0.15, transient_failures=5, seed=2)
    srv = _server(cfg, faults=spec, retry_limit=2, retry_backoff_s=0.0)
    res = _serve(srv, streams)
    poisoned = [sid for sid, r in res.items() if r.poisoned]
    assert poisoned, "0.15 fault rate with 5x persistence must exhaust " \
                     "the 2-retry budget somewhere"
    for sid, r in res.items():
        if not r.poisoned:
            np.testing.assert_array_equal(_preds(r), bp[sid])
        else:
            assert "retry limit" in r.failure


# --------------------------------------------------------------------------
# ServeError: attribution + partial results
# --------------------------------------------------------------------------

def test_serve_error_attributes_bucket_session_round(base3):
    cfg, streams, _, _ = base3
    srv = _server(cfg)
    srv.add_session(streams[0], n_frames=8)

    def boom(fb, by_sid):
        raise RuntimeError("encode died")
    srv._finish = boom
    with pytest.raises(ServeError, match="encode died") as ei:
        srv.serve()
    e = ei.value
    assert "bucket k=" in str(e) and "round" in str(e)
    assert e.context["sessions"] == [0]
    assert e.context["round"] == 0
    assert srv._sessions == [] and srv._inflight is None


def test_serve_error_carries_partials_for_drained_sessions(base3):
    """When the loop dies after one session fully drained, that session's
    finished StreamResult rides out on the ServeError instead of being
    thrown away with the wreckage."""
    cfg, streams, bp, _ = base3
    srv = _server(cfg)
    srv.add_session(streams[0], n_frames=8)    # drains quickly
    s1 = srv.add_session(streams[1], n_frames=N_FRAMES)
    real = srv._finish

    def sabotage(fb, by_sid):
        owners = {sid for sid, _ in fb.frame_idx}
        if owners == {s1.sid} and s1.acct.frames >= 16:
            raise RuntimeError("device lost")
        return real(fb, by_sid)
    srv._finish = sabotage
    with pytest.raises(ServeError, match="device lost") as ei:
        srv.serve()
    partial = ei.value.partial_results
    assert list(partial) == [0]
    assert partial[0].frames == 8
    np.testing.assert_array_equal(_preds(partial[0], 8), bp[0][:8])


# --------------------------------------------------------------------------
# checkpoint round-trip, migration, restarts
# --------------------------------------------------------------------------

def test_checkpoint_roundtrip_bitwise(tmp_path, base3):
    """Satellite (c): pause -> checkpoint -> restore in a fresh server;
    the resumed serve's predictions, accounting totals, and mask-cache
    hit behavior all match the uninterrupted run exactly."""
    cfg, streams, bp, base = base3
    srv = _server(cfg)
    for st in streams:
        srv.add_session(st, n_frames=N_FRAMES)
    assert srv.serve(max_rounds=1) == {}       # paused mid-stream
    srv.checkpoint(root=str(tmp_path))

    srv2 = _server(cfg)
    sessions = srv2.restore_checkpoint(str(tmp_path),
                                       streams=dict(enumerate(streams)))
    assert sorted(sessions) == [0, 1, 2]
    res = srv2.serve()
    for sid in bp:
        np.testing.assert_array_equal(_preds(res[sid]), bp[sid])
        assert res[sid].frames == base[sid].frames
        assert res[sid].scored_frames == base[sid].scored_frames
        assert res[sid].reused_frames == base[sid].reused_frames
        assert res[sid].bucket_hits == base[sid].bucket_hits
        assert res[sid].mean_frame_uj == base[sid].mean_frame_uj


@pytest.mark.parametrize("force_bucket,refresh", [(0.5, 8), (0.5, 16),
                                                   (0.0, 16)])
def test_checkpoint_roundtrip_device_ref_scores_bitwise(tmp_path, base3,
                                                        force_bucket,
                                                        refresh):
    """The mask caches' reference scores live on the device mid-stream; a
    checkpoint pulls them, and the restored serve (host reference scores
    until the next scored chunk) resumes bitwise, pinned or score-driven,
    through chunks that score nothing (refresh 16 over chunks of 8)."""
    import jax
    cfg, streams, _, _ = base3
    kw = dict(force_bucket=force_bucket, mask_refresh=refresh,
              delta_threshold=1e9 if refresh > 8 else 0.15)
    whole = _serve(_server(cfg, **kw), streams)
    srv = _server(cfg, **kw)
    live = [srv.add_session(st, n_frames=N_FRAMES) for st in streams]
    assert srv.serve(max_rounds=1) == {}
    assert all(isinstance(s.cache._ref_scores, jax.Array) for s in live)
    srv.checkpoint(root=str(tmp_path))
    srv2 = _server(cfg, **kw)
    srv2.restore_checkpoint(str(tmp_path), streams=dict(enumerate(streams)))
    res = srv2.serve()
    for sid, r in whole.items():
        np.testing.assert_array_equal(_preds(res[sid]), _preds(r))
        assert res[sid].scored_frames == r.scored_frames
        assert res[sid].reused_frames == r.reused_frames
    pulls = srv.spans.gate_pulls + srv2.spans.gate_pulls
    launches = srv.spans.score_launches + srv2.spans.score_launches
    assert pulls == (0 if force_bucket else launches)


@pytest.mark.slow
def test_checkpoint_roundtrip_noisy_drift_bitwise(tmp_path):
    """Under calibrated noise the server-owned DriftState (thermal time
    index) must round-trip bitwise: the resumed noisy serve equals the
    uninterrupted noisy serve frame-for-frame."""
    cfg = _smoke_cfg("photonic_pallas").with_(
        noise=NoiseSpec(drift_rate_nm=0.002, seed=3))
    streams = video_fleet(2, img_size=cfg.img_size, patch=cfg.patch)
    base = _serve(_server(cfg), streams)
    srv = _server(cfg)
    for st in streams:
        srv.add_session(st, n_frames=N_FRAMES)
    assert srv.serve(max_rounds=1) == {}
    srv.checkpoint(root=str(tmp_path))
    srv2 = _server(cfg)
    srv2.restore_checkpoint(str(tmp_path), streams=dict(enumerate(streams)))
    assert np.asarray(srv2.drift.frame) == np.asarray(srv.drift.frame)
    res = srv2.serve()
    for sid, r in base.items():
        np.testing.assert_array_equal(_preds(res[sid]), _preds(r))
    # thermal time index ends exactly where the uninterrupted run's does
    assert int(np.asarray(srv2.drift.frame)) == N_FRAMES * 2
    assert float(np.asarray(srv2.drift.drift_nm)) == pytest.approx(
        N_FRAMES * 2 * cfg.noise.drift_rate_nm, abs=1e-5)


def test_migration_export_adopt_bitwise(base3):
    cfg, streams, bp, _ = base3
    srv_a = _server(cfg)
    for st in streams:
        srv_a.add_session(st, n_frames=N_FRAMES)
    assert srv_a.serve(max_rounds=1) == {}
    snap = srv_a.export_session(1)
    assert snap["meta"]["sid"] == 1
    srv_b = _server(cfg)
    srv_b.adopt_session(snap, stream=streams[1])
    res_b = srv_b.serve()
    res_a = srv_a.serve()
    np.testing.assert_array_equal(_preds(res_b[1]), bp[1])
    np.testing.assert_array_equal(_preds(res_a[0]), bp[0])
    np.testing.assert_array_equal(_preds(res_a[2]), bp[2])
    assert 1 not in res_a


def test_checkpoint_refused_under_mix_streams(base3):
    cfg, streams, _, _ = base3
    srv = _server(cfg, mix_streams=True)
    srv.add_session(streams[0], n_frames=8)
    with pytest.raises(ValueError, match="mix_streams"):
        srv.checkpoint(root="/tmp/nope")


def test_checkpoint_fault_degrades_gracefully(tmp_path, base3):
    """Checkpoint I/O loss must not take serving down: the round keeps
    going on the last good snapshot and the failure is counted."""
    cfg, streams, bp, _ = base3
    srv = _server(cfg, faults=FaultSpec(checkpoint_fault_rate=1.0, seed=4),
                  checkpoint_dir=str(tmp_path), checkpoint_every=1)
    res = _serve(srv, streams)
    assert srv.checkpoint_failures > 0
    for sid in bp:
        np.testing.assert_array_equal(_preds(res[sid]), bp[sid])


def test_serve_with_restarts_resumes_bitwise(tmp_path, base3):
    cfg, streams, bp, base = base3

    def make_server(attempt):
        faults = FaultSpec(crash_at_round=2, seed=5) if attempt == 0 else None
        return _server(cfg, faults=faults, checkpoint_dir=str(tmp_path),
                       checkpoint_every=1)

    def register(srv):
        for st in streams:
            srv.add_session(st, n_frames=N_FRAMES)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        res, restarts, _ = serve_with_restarts(
            make_server, register, str(tmp_path),
            streams=dict(enumerate(streams)))
    assert restarts == 1
    for sid in bp:
        np.testing.assert_array_equal(_preds(res[sid]), bp[sid])
        assert res[sid].frames == base[sid].frames


# --------------------------------------------------------------------------
# graceful degradation: load shedding + flush watchdog
# --------------------------------------------------------------------------

def test_load_shedding_bounds_queue_and_accounts_drops(base3):
    cfg, streams, _, _ = base3
    srv = _server(cfg, max_pending_rows=4)
    res = _serve(srv, streams[:2])
    assert sum(r.shed_frames for r in res.values()) > 0
    for r in res.values():
        assert r.frames + r.shed_frames == N_FRAMES
        assert not r.poisoned


def test_watchdog_flags_injected_stragglers(base3):
    cfg, streams, _, _ = base3
    srv = _server(cfg, watchdog=True,
                  faults=FaultSpec(stall_rate=0.15, stall_s=0.05, seed=6))
    _serve(srv, streams)
    assert srv.telemetry is not None
    assert srv.telemetry.total_recorded >= 10
    assert len(srv.straggler_flags) > 0
    # flagged observations really are the stalled flushes: each took
    # longer than the stall floor
    assert all(o.wall_s >= 0.05 for o in srv.straggler_flags)
