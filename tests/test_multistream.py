"""Multi-stream session server tests: interleaved-vs-sequential bitwise
parity (per backend combo), per-session fairness under a bursty stream,
deadline-flush padding hygiene, warm-start jit ladder, dead-bucket
trimming, the scheduler's row storage + flush_stale surfaces, and the
mesh-sharded encode path (subprocess, forced multi-device host)."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import VideoStream, video_fleet
from repro.serving.buckets import BucketLadder
from repro.serving.engine import ServingEngine, _smoke_cfg
from repro.serving.scheduler import MicroBatcher
from repro.serving.server import (ServerConfig, StreamServer,
                                  interleave_rounds)
from repro.serving.session import ServingConfig


# --------------------------------------------------------------------------
# scheduler: row storage, flush_stale, drain(select)
# --------------------------------------------------------------------------

def test_push_stores_bare_rows_until_flush():
    """Single-frame pushes keep the bare (k, d) row in the queue (no
    per-frame [None] copy); rank expansion happens once, at flush."""
    mb = MicroBatcher(microbatch=3)
    rows = [jnp.full((2, 5), float(i)) for i in range(3)]
    assert mb.push(8, rows[0], 0) == []
    assert mb.push(8, rows[1], 1) == []
    (tokens, idxs, _, is_row), = mb._queues[8][:1]
    assert is_row and tokens.shape == (2, 5)         # still a bare row
    (fb,) = mb.push(8, rows[2], 2)
    assert fb.n_real == 3 and fb.frame_idx == [0, 1, 2]
    np.testing.assert_array_equal(np.asarray(fb.tokens),
                                  np.stack([np.asarray(r) for r in rows]))


def test_push_rows_and_groups_mix_in_order():
    mb = MicroBatcher(microbatch=4)
    group = jnp.arange(2 * 3 * 2, dtype=jnp.float32).reshape(2, 3, 2)
    row = jnp.full((3, 2), 9.0)
    assert mb.push_many(4, group, [0, 1]) == []
    assert mb.push(4, row, 2) == []
    (fb,) = mb.push(4, row + 1, 3)
    assert fb.frame_idx == [0, 1, 2, 3]
    np.testing.assert_array_equal(np.asarray(fb.tokens[2]), np.asarray(row))


def test_flush_stale_honors_deadline_and_pads():
    mb = MicroBatcher(microbatch=4)
    old = jnp.ones((2, 3, 2))
    new = jnp.ones((1, 3, 2))
    mb.push_many(8, old, [0, 1], now=0)
    mb.push_many(16, new, [2], now=5)
    assert mb.flush_stale(-1) == []                  # nothing old enough
    (fb,) = mb.flush_stale(0)                        # only the now=0 queue
    assert fb.bucket == 8 and fb.n_real == 2
    assert fb.tokens.shape == (4, 3, 2)              # padded to microbatch
    assert float(fb.tokens[2:].sum()) == 0.0
    assert mb.pending == 1                           # now=5 queue untouched
    (fb2,) = mb.flush_stale(5)
    assert fb2.bucket == 16 and fb2.n_real == 1


def test_flush_stale_oldest_queue_first():
    mb = MicroBatcher(microbatch=4)
    mb.push_many(16, jnp.ones((1, 2, 2)), [0], now=3)
    mb.push_many(8, jnp.ones((1, 2, 2)), [1], now=1)
    out = mb.flush_stale(10)
    assert [fb.bucket for fb in out] == [8, 16]      # by age, not key


def test_drain_select_isolates_one_sessions_queues():
    """The server drains a finished session's (bucket, sid) queues without
    touching other sessions' pending frames."""
    mb = MicroBatcher(microbatch=4)
    mb.push_many((8, 0), jnp.ones((2, 3, 2)), [(0, 0), (0, 1)])
    mb.push_many((8, 1), jnp.ones((1, 3, 2)), [(1, 0)])
    out = mb.drain(select=lambda key: key[1] == 0)
    assert [fb.bucket for fb in out] == [(8, 0)]
    assert out[0].n_real == 2
    assert mb.pending == 1                           # session 1 still queued
    assert mb.pending_keys() == ((8, 1),)


# --------------------------------------------------------------------------
# bucket-ladder trimming
# --------------------------------------------------------------------------

def test_ladder_trim_drops_dead_sizes():
    lad = BucketLadder((9, 18, 27, 36))
    t = lad.trim((9, 27))
    assert t.sizes == (18, 36)
    # budgets that routed to a dropped size route up to the next survivor
    assert t.route(5) == 18 and t.route(20) == 36


def test_ladder_trim_keeps_cap_by_default():
    lad = BucketLadder((9, 18, 36))
    assert lad.trim((18, 36)).sizes == (9, 36)       # cap survives
    assert lad.trim((18, 36), keep_cap=False).sizes == (9,)
    assert lad.trim((99,)).sizes == lad.sizes        # unknown sizes ignored
    with pytest.raises(ValueError):
        lad.trim((9, 18, 36), keep_cap=False)


def test_calibrate_trim_without_sessions_is_a_no_op():
    """An empty calibration pass must not collapse the ladder to the cap
    (no sessions -> no evidence any bucket is dead)."""
    cfg = _smoke_cfg("bf16")
    srv = StreamServer(cfg, ServerConfig(microbatch=4, chunk=8,
                                         warm_start=False), n_classes=8)
    before = srv.ladder.sizes
    assert srv.calibrate_trim() == ()
    assert srv.ladder.sizes == before


def test_server_config_from_serving_preserves_server_fields():
    """from_serving on an object that already is a ServerConfig keeps its
    server-specific knobs; only the overrides change."""
    sc = ServerConfig(microbatch=8, max_wait_chunks=3, mix_streams=True,
                      mesh="off")
    out = ServerConfig.from_serving(sc, warm_start=False)
    assert (out.max_wait_chunks, out.mix_streams, out.mesh) == (3, True,
                                                                "off")
    assert out.microbatch == 8 and out.warm_start is False
    plain = ServerConfig.from_serving(ServingConfig(microbatch=2),
                                      mesh="off")
    assert plain.microbatch == 2 and plain.max_wait_chunks == 0


def test_server_calibrate_trim_shrinks_warmed_jit_set():
    cfg = _smoke_cfg("bf16")
    srv = StreamServer(cfg, ServerConfig(microbatch=4, chunk=8,
                                         warm_start=False), n_classes=8)
    for st in video_fleet(2, img_size=32, patch=8, seed=0, cut_every=16):
        srv.add_session(st, n_frames=16)
    full = set(srv.ladder.sizes)
    removed = srv.calibrate_trim()
    assert set(srv.ladder.sizes) == full - set(removed)
    assert set(srv._gather) == set(srv.ladder.sizes)  # jits dropped too
    results = srv.serve()
    for res in results.values():
        assert res.frames == 16
        assert set(res.bucket_hits) == set(srv.ladder.sizes)
        assert sum(res.bucket_hits.values()) == 16


# --------------------------------------------------------------------------
# fleet factory
# --------------------------------------------------------------------------

def test_video_fleet_streams_are_distinct_and_deterministic():
    a, b = video_fleet(2, img_size=32, patch=8, seed=7)
    assert a.seed != b.seed
    fa = a.frames_at(0, 4)["frames"]
    fb = b.frames_at(0, 4)["frames"]
    assert np.abs(fa - fb).max() > 0.5               # different scenes
    again = video_fleet(2, img_size=32, patch=8, seed=7)[0]
    np.testing.assert_array_equal(fa, again.frames_at(0, 4)["frames"])
    with pytest.raises(ValueError):
        video_fleet(0, img_size=32)


# --------------------------------------------------------------------------
# interleaved-vs-sequential bitwise parity
# --------------------------------------------------------------------------

def _parity_case(backend, attn, ffn, n_streams=2, n_frames=16, phase=4):
    """Interleaved N-stream serving must be bit-identical, per stream, to N
    sequential single-stream runs: session-pure micro-batches mean every
    encode launch contains exactly the frames a solo run would co-batch,
    so per-launch w8a8 activation absmax scopes never couple streams."""
    cfg = _smoke_cfg(backend, attn, ffn)
    sc = ServingConfig(microbatch=4, chunk=8)
    fleet = video_fleet(n_streams, img_size=32, patch=8, seed=0,
                        cut_every=16)
    seq = [ServingEngine(cfg, sc, n_classes=8, seed=0).run(
        st, n_frames=n_frames, start=phase * i)
        for i, st in enumerate(fleet)]
    srv = StreamServer(cfg, ServerConfig.from_serving(sc), n_classes=8,
                       seed=0)
    sessions = [srv.add_session(st, n_frames=n_frames, start=phase * i)
                for i, st in enumerate(fleet)]
    res = srv.serve()
    for i, s in enumerate(sessions):
        assert res[s.sid].predictions == seq[i].predictions, (
            backend, attn, ffn, i)
        assert res[s.sid].bucket_hits == seq[i].bucket_hits
        assert res[s.sid].bucket_launches == seq[i].bucket_launches
        assert res[s.sid].scored_frames == seq[i].scored_frames
        assert res[s.sid].mean_frame_uj == pytest.approx(
            seq[i].mean_frame_uj)


@pytest.mark.parametrize("backend,attn,ffn", [
    ("bf16", "", ""),
    ("photonic_sim", "", ""),
    ("photonic_pallas", "", ""),
])
def test_interleaved_matches_sequential(backend, attn, ffn):
    _parity_case(backend, attn, ffn)


@pytest.mark.slow
@pytest.mark.parametrize("backend,attn,ffn", [
    ("photonic_pallas", "flash", ""),
    ("photonic_pallas", "flash", "fused"),   # the acceptance path
    ("bf16", "xla", ""),
    ("photonic_sim", "", "xla"),
])
def test_interleaved_matches_sequential_fused(backend, attn, ffn):
    _parity_case(backend, attn, ffn, n_streams=3)


def test_warm_start_is_numerics_neutral_and_compiles_ladder():
    cfg = _smoke_cfg("photonic_sim")
    stream = VideoStream(img_size=32, patch=8, cut_every=16)
    cold_srv = StreamServer(cfg, ServerConfig(microbatch=4, chunk=8,
                                              warm_start=False), n_classes=8)
    s0 = cold_srv.add_session(stream, n_frames=16)
    cold = cold_srv.serve()[s0.sid]
    warm_srv = StreamServer(cfg, ServerConfig(microbatch=4, chunk=8),
                            n_classes=8)
    assert warm_srv.warm_s > 0                       # eager startup compile
    s1 = warm_srv.add_session(stream, n_frames=16)
    warm = warm_srv.serve()[s1.sid]
    assert warm.predictions == cold.predictions
    assert warm.bucket_hits == cold.bucket_hits


# --------------------------------------------------------------------------
# fairness + deadline
# --------------------------------------------------------------------------

def test_interleave_rounds_round_robins_backlogs():
    assert interleave_rounds([["a1", "a2", "a3"], ["b1"]]) == [
        "a1", "b1", "a2", "a3"]
    assert interleave_rounds([[], ["b1", "b2"], ["c1"]]) == [
        "b1", "c1", "b2"]
    assert interleave_rounds([]) == []
    assert interleave_rounds([[], []]) == []


def test_bursty_stream_cannot_starve_short_stream():
    """Session A has 3x the frames of B, all pinned to one bucket (two
    ready flushes per round each). While B is still serving, A's executed
    launches may lead B's by at most one scheduling round's worth — A's
    backlog never runs ahead of B's service."""
    cfg = _smoke_cfg("bf16")
    srv = StreamServer(cfg, ServerConfig(microbatch=4, chunk=8,
                                         force_bucket=1.0,
                                         warm_start=False), n_classes=8)
    a, b = video_fleet(2, img_size=32, patch=8, seed=0, cut_every=16)
    sa = srv.add_session(a, n_frames=48)
    sb = srv.add_session(b, n_frames=16)
    res = srv.serve()
    assert res[sa.sid].frames == 48 and res[sb.sid].frames == 16
    sids = [owners[0] for owners, _, _ in srv.flush_log]
    last_b = max(i for i, sid in enumerate(sids) if sid == sb.sid)
    a_before = sum(1 for sid in sids[:last_b] if sid == sa.sid)
    b_before = sum(1 for sid in sids[:last_b] if sid == sb.sid)
    # equal service rate while both live: chunk/microbatch = 2 per round
    assert a_before <= b_before + 2, (sids,)


def test_deadline_flush_bounds_wait_without_leaking_padding():
    """max_wait_chunks pad-flushes partial micro-batches; padded rows must
    never surface in accounting (frames, energy) or predictions. Routing
    happens before batching, so the modeled per-frame energy is identical
    to the no-deadline run even though launch compositions differ."""
    cfg = _smoke_cfg("bf16")
    stream = VideoStream(img_size=32, patch=8, seed=2, cut_every=16)

    def run(max_wait):
        # chunk (3) < microbatch (8): arrivals alone never fill a batch in
        # one round, so partial queues survive rounds and the deadline has
        # something to flush mid-stream
        srv = StreamServer(cfg, ServerConfig(
            microbatch=8, chunk=3, force_bucket=1.0,
            max_wait_chunks=max_wait, warm_start=False), n_classes=8)
        s = srv.add_session(stream, n_frames=12)
        return srv.serve()[s.sid], srv

    free, srv_free = run(0)
    tight, srv_tight = run(1)
    for res in (free, tight):
        assert res.frames == 12
        assert sorted(res.predictions) == list(range(12))
        assert sum(res.bucket_hits.values()) == 12
    assert tight.bucket_hits == free.bucket_hits     # routing unchanged
    assert tight.mean_frame_uj == pytest.approx(free.mean_frame_uj)
    # the deadline fired mid-stream: more short (padded) launches than the
    # no-deadline run's single end-of-stream drain...
    tight_partial = [n for _, _, n in srv_tight.flush_log if n < 8]
    free_partial = [n for _, _, n in srv_free.flush_log if n < 8]
    assert len(tight_partial) > len(free_partial) >= 1
    # ...and a frame queued at round r is served within max_wait rounds:
    # no launch ever carries more than max_wait+1 rounds' worth of arrivals
    assert max(n for _, _, n in srv_tight.flush_log) <= 2 * 3


def test_mid_serve_failure_poisons_half_served_sessions():
    """A serve() that dies mid-stream must not leave resumable-looking
    sessions behind: their accounting is partial, and re-opening them
    would re-ingest from frame 0 and double-count. They are abandoned;
    fresh sessions serve cleanly afterwards."""
    cfg = _smoke_cfg("bf16")
    srv = StreamServer(cfg, ServerConfig(microbatch=4, chunk=8,
                                         warm_start=False), n_classes=8)
    stream = VideoStream(img_size=32, patch=8, cut_every=16)
    s = srv.add_session(stream, n_frames=16)

    def boom(fb, by_sid):
        raise RuntimeError("encode died")

    real_finish = srv._finish
    srv._finish = boom
    with pytest.raises(RuntimeError, match="encode died"):
        srv.serve()
    assert s.finished                       # poisoned, never re-served
    assert srv._sessions == []
    srv._finish = real_finish
    s2 = srv.add_session(stream, n_frames=8)
    res = srv.serve()
    assert list(res) == [s2.sid]
    assert res[s2.sid].frames == 8


# --------------------------------------------------------------------------
# mixed-stream micro-batches (opt-in)
# --------------------------------------------------------------------------

def test_mix_streams_fills_across_sessions():
    """mix_streams=True genuinely co-batches sessions (fewer launches than
    session-pure) and still serves every frame exactly once. On the float
    backend each row's result is independent of its co-batched rows, so
    predictions stay bit-identical to sequential runs even when mixed."""
    cfg = _smoke_cfg("bf16")
    sc = ServingConfig(microbatch=4, chunk=8)
    fleet = video_fleet(2, img_size=32, patch=8, seed=3, cut_every=16)
    seq = [ServingEngine(cfg, sc, n_classes=8, seed=0).run(st, n_frames=16)
           for st in fleet]
    srv = StreamServer(cfg, ServerConfig.from_serving(
        sc, mix_streams=True, warm_start=False), n_classes=8, seed=0)
    sessions = [srv.add_session(st, n_frames=16) for st in fleet]
    res = srv.serve()
    assert any(len(owners) > 1 for owners, _, _ in srv.flush_log)
    pure_launches = sum(sum(r.bucket_launches.values()) for r in seq)
    assert len(srv.flush_log) <= pure_launches
    for i, s in enumerate(sessions):
        assert res[s.sid].frames == 16
        assert res[s.sid].predictions == seq[i].predictions


# --------------------------------------------------------------------------
# mesh-sharded encode (forced multi-device CPU host, subprocess)
# --------------------------------------------------------------------------

_MESH_SCRIPT = """
import json, sys
from repro.data.pipeline import video_fleet
from repro.serving.engine import _smoke_cfg
from repro.serving.server import ServerConfig, StreamServer
import jax
mode = sys.argv[1]
cfg = _smoke_cfg("photonic_sim")
srv = StreamServer(cfg, ServerConfig(microbatch=4, chunk=8, mesh=mode,
                                     warm_start=False), n_classes=8)
if mode == "auto":
    assert srv.mesh is not None and len(jax.devices()) == 2, jax.devices()
else:
    assert srv.mesh is None
sessions = [srv.add_session(st, n_frames=16)
            for st in video_fleet(2, img_size=32, patch=8, seed=0,
                                  cut_every=16)]
res = srv.serve()
print(json.dumps({str(s.sid): res[s.sid].predictions for s in sessions}))
"""


@pytest.mark.slow
def test_mesh_sharded_encode_matches_single_device():
    """With XLA forced to expose 2 host devices, the server shards the
    encode batch axis over the ("data",) mesh; predictions must match the
    single-device run exactly (integer accumulates are placement-
    invariant; per-frame float epilogues are row-local)."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=2"))
    outs = {}
    for mode in ("auto", "off"):
        proc = subprocess.run(
            [sys.executable, "-c", _MESH_SCRIPT, mode],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs[mode] = json.loads(proc.stdout.strip().splitlines()[-1])
    assert outs["auto"] == outs["off"]


_PINNED_MESH_SCRIPT = """
import json, sys
from repro.data.pipeline import video_fleet
from repro.serving.engine import _smoke_cfg
from repro.serving.server import ServerConfig, StreamServer
mode = sys.argv[1]
cfg = _smoke_cfg("photonic_sim")
srv = StreamServer(cfg, ServerConfig(microbatch=4, chunk=8, mesh=mode,
                                     force_bucket=0.5), n_classes=8)
sessions = [srv.add_session(st, n_frames=16)
            for st in video_fleet(2, img_size=32, patch=8, seed=0,
                                  cut_every=16)]
srv.serve(max_rounds=1)
# mid-stream, the reference scores sit on every device of the mesh
ref = sessions[0].cache._ref_scores
on = len(ref.sharding.device_set)
res = srv.serve()
print(json.dumps({"predictions": {str(s.sid): res[s.sid].predictions
                                  for s in sessions},
                  "ref_devices": on,
                  "gate_pulls": srv.spans.gate_pulls}))
"""


@pytest.mark.slow
def test_mesh_device_gate_matches_single_device():
    """The gate's device programs (MGNet's input gather, the score
    selection) run whole on each device of a forced 2-device data mesh,
    warmed at start-up: predictions equal the single-device run's, and
    under a pin no score crosses to the host."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=2"))
    outs = {}
    for mode in ("auto", "off"):
        proc = subprocess.run(
            [sys.executable, "-c", _PINNED_MESH_SCRIPT, mode],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs[mode] = json.loads(proc.stdout.strip().splitlines()[-1])
    assert outs["auto"]["predictions"] == outs["off"]["predictions"]
    assert (outs["auto"]["ref_devices"], outs["off"]["ref_devices"]) == (2, 1)
    assert [o["gate_pulls"] for o in outs.values()] == [0, 0]


_MODEL_MESH_SCRIPT = """
import json, sys
import jax
from repro.data.pipeline import video_fleet
from repro.serving.engine import _smoke_cfg
from repro.serving.server import ServerConfig, StreamServer

shards = int(sys.argv[1])
cfg = _smoke_cfg("photonic_pallas", "flash", "fused")
srv = StreamServer(cfg, ServerConfig(microbatch=4, chunk=8,
                                     warm_start=False,
                                     mesh="auto" if shards else "off",
                                     model_shards=shards, one_shape=True),
                   n_classes=8)
if shards:
    assert srv.mesh is not None and len(jax.devices()) == 4, jax.devices()
    assert tuple(srv.mesh.axis_names) == ("data", "model"), srv.mesh
else:
    assert srv.mesh is None
sessions = [srv.add_session(st, n_frames=16)
            for st in video_fleet(2, img_size=32, patch=8, seed=0,
                                  cut_every=16)]
res = srv.serve()
from repro.models.sharded_encoder import sharded_encoder_cache_size
print(json.dumps({
    "predictions": {str(s.sid): res[s.sid].predictions for s in sessions},
    "sharded_jits": sharded_encoder_cache_size(),
}))
"""


@pytest.mark.slow
def test_model_sharded_fused_encode_matches_single_device():
    """The tentpole contract: the fully-fused serving combo
    (photonic_pallas + flash + fused) under model_shards=2 on a forced
    4-device 2-D ("data", "model") mesh predicts bitwise-identically to
    the unsharded fused path, and the sharded jit cache actually engages
    (a silent fallback to the unsharded encoder would make the parity
    assertion vacuous)."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"))
    outs = {}
    for shards in ("2", "0"):
        proc = subprocess.run(
            [sys.executable, "-c", _MODEL_MESH_SCRIPT, shards],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs[shards] = json.loads(proc.stdout.strip().splitlines()[-1])
    assert outs["2"]["predictions"] == outs["0"]["predictions"]
    assert outs["2"]["sharded_jits"] > 0
    assert outs["0"]["sharded_jits"] == 0
