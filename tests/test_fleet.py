"""Fleet front-end tests (serving/fleet.py): placement, migration, drain,
aggregated warnings. In-process workers, tiny bf16 configs — the forced
multi-device scaling/parity gates live in benchmarks/fleet_bench.py."""

import warnings

import pytest

from repro.data.pipeline import video_fleet
from repro.serving.engine import ServingEngine, _smoke_cfg
from repro.serving.fleet import _SID_STRIDE, FleetRouter
from repro.serving.server import ServerConfig
from repro.serving.session import ServingConfig


def _cfg():
    return _smoke_cfg("bf16")


def _sc(**kw):
    return ServerConfig.from_serving(
        ServingConfig(microbatch=4, chunk=8), warm_start=False, **kw)


def _solo(cfg, streams, n_frames=16):
    return [ServingEngine(cfg, ServingConfig(microbatch=4, chunk=8),
                          n_classes=8, seed=0).run(st, n_frames=n_frames)
            for st in streams]


# -- construction / placement ---------------------------------------------


def test_bad_args_raise():
    with pytest.raises(ValueError, match="at least one"):
        FleetRouter(_cfg(), _sc(), workers=0, price_per_frame=1.0)
    with pytest.raises(ValueError, match="placement"):
        FleetRouter(_cfg(), _sc(), workers=2, placement="random",
                    price_per_frame=1.0)


def test_workers_get_disjoint_sid_ranges():
    r = FleetRouter(_cfg(), _sc(), workers=3, n_classes=8,
                    price_per_frame=1.0)
    assert [w._next_sid for w in r.workers] == [0, _SID_STRIDE,
                                               2 * _SID_STRIDE]


def test_cost_placement_beats_round_robin_assignment():
    """On a skewed mix, cost placement spreads predicted seconds while rr
    stacks the heavies; price_per_frame=1.0 makes cost == frame count."""
    streams = video_fleet(4, img_size=32, patch=8, seed=0, cut_every=16)
    frames = [30, 10, 10, 10]

    cost = FleetRouter(_cfg(), _sc(), workers=2, n_classes=8,
                       price_per_frame=1.0)
    for st, nf in zip(streams, frames):
        cost.add_job(st, n_frames=nf)
    # job 0 (30) -> w0, everything else piles onto the colder w1
    assert [j.worker for j in cost.jobs.values()] == [0, 1, 1, 1]
    assert cost.queued_seconds(0) == 30.0
    assert cost.queued_seconds(1) == 30.0
    assert cost.queued_frames(0) == 30

    rr = FleetRouter(_cfg(), _sc(), workers=2, n_classes=8,
                     placement="rr", price_per_frame=1.0)
    for st, nf in zip(streams, frames):
        rr.add_job(st, n_frames=nf)
    assert [j.worker for j in rr.jobs.values()] == [0, 1, 0, 1]
    assert rr.queued_seconds(0) == 40.0      # the rr hot spot

    # cost placement's max queue is strictly lower
    assert (max(cost.queued_seconds(i) for i in range(2)) <
            max(rr.queued_seconds(i) for i in range(2)))


# -- serving --------------------------------------------------------------


@pytest.mark.filterwarnings("ignore:fleet dead buckets")
def test_serve_matches_solo_engine():
    """Fleet-served predictions are identical to per-stream solo engine
    runs (micro-batches are session-pure) and jobs are marked done."""
    cfg = _cfg()
    streams = video_fleet(2, img_size=32, patch=8, seed=3, cut_every=16)
    solo = _solo(cfg, streams)
    r = FleetRouter(cfg, _sc(), workers=2, n_classes=8, price_per_frame=1.0)
    jobs = [r.add_job(st, n_frames=16) for st in streams]
    res = r.serve()
    assert {jobs[0].worker, jobs[1].worker} == {0, 1}
    for i, j in enumerate(jobs):
        assert j.done and res[j.job_id].frames == 16
        assert res[j.job_id].predictions == solo[i].predictions
    assert r.aggregate_fps > 0
    assert len(r.last_walls) == 2


# -- migration / rebalance / drain ----------------------------------------


@pytest.mark.filterwarnings("ignore:fleet dead buckets")
def test_migrate_preserves_predictions():
    cfg = _cfg()
    streams = video_fleet(2, img_size=32, patch=8, seed=3, cut_every=16)
    solo = _solo(cfg, streams)
    r = FleetRouter(cfg, _sc(), workers=2, n_classes=8, price_per_frame=1.0)
    jobs = [r.add_job(st, n_frames=16) for st in streams]
    moved = r.migrate(jobs[0].job_id, 1)     # both now on worker 1
    assert moved.worker == 1
    assert r.migrate(jobs[1].job_id, jobs[1].worker) is jobs[1]   # no-op
    res = r.serve()
    for i, j in enumerate(jobs):
        assert j.worker == 1
        assert res[j.job_id].predictions == solo[i].predictions
    with pytest.raises(ValueError, match="already served"):
        r.migrate(jobs[0].job_id, 0)


def test_rebalance_moves_smallest_improving_job():
    streams = video_fleet(4, img_size=32, patch=8, seed=0, cut_every=16)
    r = FleetRouter(_cfg(), _sc(), workers=2, n_classes=8,
                    placement="rr", price_per_frame=1.0)
    jobs = [r.add_job(st, n_frames=nf)
            for st, nf in zip(streams, [30, 10, 10, 10])]
    # rr: w0 = {30, 10} = 40s, w1 = {10, 10} = 20s; gap 20 -> moving the
    # 10s job equalizes (|20 - 2*10| = 0), after which no move improves
    moved = r.rebalance()
    assert moved == [jobs[2].job_id]
    assert r.queued_seconds(0) == r.queued_seconds(1) == 30.0
    assert r.rebalance() == []               # already balanced


@pytest.mark.filterwarnings("ignore:fleet dead buckets")
def test_drain_preserves_predictions(tmp_path):
    cfg = _cfg()
    streams = video_fleet(2, img_size=32, patch=8, seed=3, cut_every=16)
    solo = _solo(cfg, streams)
    r = FleetRouter(cfg, _sc(checkpoint_dir=str(tmp_path)), workers=2,
                    n_classes=8, price_per_frame=1.0)
    jobs = [r.add_job(st, n_frames=16) for st in streams]
    old = r.workers[0]
    repl = r.drain(0, root=str(tmp_path))
    assert r.workers[0] is repl and repl is not old
    res = r.serve()
    for i, j in enumerate(jobs):
        assert res[j.job_id].predictions == solo[i].predictions


# -- aggregated dead-bucket warning ---------------------------------------


def test_dead_bucket_warning_aggregated():
    """Workers serve with per-session warnings muted; the router emits ONE
    UserWarning naming every (worker, dead buckets) pair."""
    cfg = _cfg()
    sc = ServerConfig.from_serving(
        ServingConfig(microbatch=4, chunk=8, force_bucket=0.5),
        warm_start=False)
    r = FleetRouter(cfg, sc, workers=2, n_classes=8, price_per_frame=1.0)
    for st in video_fleet(2, img_size=32, patch=8, seed=0, cut_every=16):
        r.add_job(st, n_frames=16)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        r.serve()
    dead = [w for w in rec if "fleet dead buckets" in str(w.message)]
    assert len(dead) == 1
    assert "worker 0" in str(dead[0].message)
    assert "worker 1" in str(dead[0].message)

