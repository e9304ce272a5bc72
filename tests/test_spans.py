"""The serving loop's spans and counters (``serving/spans.py``): off they
record nothing and build no profiler annotation; on they nest as the loop
does; the counters agree with what ``serve()`` returns; a timed flush
feeds ``FlushTelemetry`` from the flush span's clock; a ring span lines
up with the profiler's annotation of it; and every jitted program of the
server lowers under its stable name."""

import json
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.data.pipeline import video_fleet
from repro.serving.engine import _smoke_cfg
from repro.serving.server import ServerConfig, StreamServer
from repro.serving.spans import Spans

N_FRAMES = 20           # 3 chunks of 8, the last one partial


def _server(cfg, **kw):
    base = dict(warm_start=False, mesh="off", chunk=8, microbatch=4)
    base.update(kw)
    return StreamServer(cfg, ServerConfig(**base))


def _serve(srv, streams, n_frames=N_FRAMES):
    sess = [srv.add_session(st, n_frames=n_frames) for st in streams]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        res = srv.serve()
    return sess, res


@pytest.fixture(scope="module")
def cfg():
    return _smoke_cfg("")


@pytest.fixture(scope="module")
def streams(cfg):
    return video_fleet(2, img_size=cfg.img_size, patch=cfg.patch)


def _preds(res):
    return {sid: dict(r.predictions) for sid, r in res.items()}


def test_spans_off_record_nothing_and_build_no_annotation(cfg, streams,
                                                          monkeypatch):
    srv = _server(cfg)
    assert not srv.spans.on

    def boom(*a, **k):
        raise AssertionError("TraceAnnotation built with spans off")

    with monkeypatch.context() as m:
        m.setattr(jax.profiler, "TraceAnnotation", boom)
        _, off = _serve(srv, streams)
    assert len(srv.spans.ring) == 0 and not srv.spans._open
    assert srv.spans.flushes > 0          # counters stay on
    srv.spans.enable()
    _, on = _serve(srv, streams)
    assert len(srv.spans.ring) > 0
    # the same frames served with spans on: bitwise the same classes
    assert [sorted(p.items()) for p in _preds(off).values()] == \
        [sorted(p.items()) for p in _preds(on).values()]


def test_span_tree_follows_the_loop(cfg, streams):
    srv = _server(cfg)
    srv.spans.enable()
    sess, res = _serve(srv, streams)
    ring = list(srv.spans.ring)
    sids = {s.sid for s in sess}
    by = {}
    for s in ring:
        by.setdefault(s.name, []).append(s)
    (call,) = by["serve.call"]
    assert call.parent is None
    assert {s.parent for s in by["serve.round"]} == {"serve.call"}
    for name in ("serve.ingest", "serve.gate", "serve.route",
                 "serve.flush"):
        assert {s.parent for s in by[name]} == {"serve.round"}, name
    assert {s.parent for s in by["serve.finish"]} == {"serve.call"}
    for name in ("serve.ingest", "serve.gate", "serve.route",
                 "serve.finish", "serve.session"):
        assert {s.id for s in by[name]} == sids, name
    # three chunks a session (its last partial) and one gate and route each
    assert len(by["serve.gate"]) == len(by["serve.route"]) == 3 * len(sids)
    # flushes: sequence numbers, bucket and owners as in flush_log
    assert [s.id for s in by["serve.flush"]] == \
        list(range(len(srv.flush_log)))
    assert [(s.owners, s.bucket) for s in by["serve.flush"]] == \
        [(o, k) for o, k, _ in srv.flush_log]
    # every span lies inside its parent, and the call inside nothing
    rounds = by["serve.round"]
    for s in ring:
        if s.parent == "serve.round":
            assert any(r.start_ns <= s.start_ns and s.end_ns <= r.end_ns
                       for r in rounds)
        if s.name != "serve.session":
            assert call.start_ns <= s.start_ns <= s.end_ns <= call.end_ns
    # a session runs from its first ingest to its finish, in the ring only
    for s in by["serve.session"]:
        first = min(i.start_ns for i in by["serve.ingest"] if i.id == s.id)
        fin = next(f for f in by["serve.finish"] if f.id == s.id)
        assert s.parent is None
        assert s.start_ns <= first and fin.end_ns <= s.end_ns
    srv.spans.disable()
    assert not srv.spans.on


def test_counters_agree_with_results_and_flush_log(cfg, streams):
    srv = _server(cfg)
    launches = []
    score = srv._score

    def counted(*a):
        launches.append(1)
        return score(*a)

    srv._score = counted
    sess, res = _serve(srv, streams)
    sp = srv.spans
    n_chunks = 3 * len(sess)
    assert sp.chunks_ingested == n_chunks
    frame_bytes = 8 * cfg.img_size * cfg.img_size * 3 * 4
    # the frames are put once; the scores never go back to the device
    assert sp.h2d_bytes == n_chunks * frame_bytes
    assert sp.score_launches == len(launches) > 0
    assert sp.score_launches <= sum(r.scored_frames for r in res.values())
    assert sp.flushes == len(srv.flush_log)
    assert sp.rows_real == sum(n for _, _, n in srv.flush_log) == \
        sum(r.frames for r in res.values())
    assert sp.rows_launched == 4 * sp.flushes
    # score-driven routing pulls each scored chunk's scores once
    assert sp.gate_pulls == sp.score_launches
    # untimed: the score pulls and one pull per deferred flush result
    assert sp.host_syncs == sp.gate_pulls + sp.flushes
    assert sp.counts()["flushes"] == sp.flushes


@pytest.mark.parametrize("refresh,thr", [(8, 0.15), (16, 1e9)])
def test_pinned_gate_pulls_nothing(cfg, streams, refresh, thr):
    """Under a ``force_bucket`` pin the scores never cross to the host:
    the only host syncs are the deferred-prediction pulls. Score-driven
    routing on a one-rung ladder (every frame to the same bucket) serves
    bitwise the same classes and pulls once per scored chunk — not for a
    chunk that scored nothing (refresh 16 over chunks of 8)."""
    kw = dict(bucket_fractions=(1.0,), mask_refresh=refresh,
              delta_threshold=thr)
    pinned = _server(cfg, force_bucket=1.0, **kw)
    _, res_p = _serve(pinned, streams)
    sp = pinned.spans
    assert sp.gate_pulls == 0
    assert sp.host_syncs == sp.flushes
    driven = _server(cfg, **kw)
    _, res_d = _serve(driven, streams)
    sd = driven.spans
    assert sd.gate_pulls == sd.score_launches == sp.score_launches > 0
    assert sd.host_syncs == sd.gate_pulls + sd.flushes
    if refresh > 8:
        assert sd.score_launches < sd.chunks_ingested
    assert [sorted(p.items()) for p in _preds(res_p).values()] == \
        [sorted(p.items()) for p in _preds(res_d).values()]


def test_timed_flushes_feed_telemetry_from_the_flush_span(cfg, streams):
    srv = _server(cfg, watchdog=True)
    _serve(srv, streams)
    obs = list(srv.telemetry)
    assert len(obs) == srv.telemetry.total_recorded == len(srv.flush_log)
    assert all(o.wall_s > 0 for o in obs)
    sp = srv.spans
    # every timed flush adds its block_until_ready to the host syncs
    assert sp.host_syncs == sp.gate_pulls + 2 * sp.flushes
    assert len(sp.ring) == 0              # timed, yet recorded nothing


def test_dump_writes_counters_and_ring(cfg, streams, tmp_path):
    srv = _server(cfg)
    srv.spans.enable()
    _serve(srv, streams)
    path = tmp_path / "ring.json"
    srv.spans.dump(path)
    d = json.loads(path.read_text())
    assert d["counters"] == srv.spans.counts()
    assert len(d["spans"]) == len(srv.spans.ring)
    flush = next(s for s in d["spans"] if s["name"] == "serve.flush")
    assert flush["bucket"] in srv.ladder.sizes and flush["owners"]


def test_ring_span_lines_up_with_its_annotation(tmp_path):
    """The ring and the profiler stamp a span on one clock: the ring's
    times, less the session's ``profile_start_time``, bracket the
    annotation's own."""
    from jax.profiler import ProfileData
    rec = Spans()
    rec.enable()
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with rec.span("serve.gate", 3):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    (ring,) = rec.ring
    pd = ProfileData.from_file(str(sorted(
        Path(tmp_path).rglob("*.xplane.pb"))[-1]))
    start = None
    ann = None
    for plane in pd.planes:
        start = dict(plane.stats).get("profile_start_time", start)
        for line in plane.lines:
            ann = next((e for e in line.events if e.name == "serve.gate"),
                       ann)
    assert ann is not None and start is not None
    assert dict(ann.stats)["id"] == 3
    lo = ring.start_ns - int(start)
    hi = ring.end_ns - int(start)
    # the annotation opens after the ring's start and closes before its
    # end, within a millisecond on either side
    assert lo - 1e6 <= ann.start_ns and ann.start_ns + ann.duration_ns <= \
        hi + 1e6
    assert abs(ann.start_ns - lo) < 1e6


def test_jitted_programs_lower_under_stable_names(cfg):
    srv = _server(cfg, one_shape=True)
    sc, n = srv.serve_cfg, srv.n_patches
    zf = jnp.zeros((sc.chunk, cfg.img_size, cfg.img_size, 3), jnp.float32)
    toks = jax.eval_shape(srv._embed, srv.params, zf)
    zt = jnp.zeros(toks.shape, toks.dtype)
    order = jnp.zeros((sc.chunk, n), jnp.int32)
    k = srv.ladder.cap
    enc = jnp.zeros((sc.microbatch, k, toks.shape[-1]), toks.dtype)
    mask = jnp.ones((sc.chunk, n), jnp.float32)
    lowered = {
        "opto_embed": srv._embed.lower(srv.params, zf),
        "mgnet_score": srv._score.lower(srv.params, zf),
        "gate_inputs": srv._gate_ops.inputs.lower(
            zf, jnp.zeros(sc.chunk, jnp.int32)),
        "gate_scores": srv._gate_ops.scores.lower(
            jnp.zeros(n), jnp.zeros((sc.chunk, n)),
            jnp.zeros(sc.chunk, jnp.int32)),
        "patch_order": srv._order.lower(jnp.zeros((sc.chunk, n))),
        "gather_topk": srv._gather[k].lower(zt, order),
        "opto_encode": srv._encode.lower(srv.params, enc),
        "opto_encode_dense": srv._encode_dense.lower(srv.params, zf, mask),
        "opto_encode_k": srv._encode_one[k].lower(srv.params, enc),
    }
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @jit_{name} "), name
