"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic and chip count come from
``BENCHMARK.json``; its server settings and correctness limits from
``bench/workloads/<cell>.json``. Set-up makes the weights on the device
from the seed, builds ``StreamServer``, compiles the cell's one bucket
and serves one untimed round of the traffic. The window then drives
``StreamServer.serve()`` for ``--seconds``. With ``--trace 1`` the
cell's per-layer metrics are printed: counters, clip spans and
``serve_mfu`` from the window, device numbers from about three more
seconds of the same traffic served under a device-only profiler trace
after it; with ``--trace 0`` its end-to-end metrics.

After the window (and after the peak device memory was read and the
server freed) a sample of the served frames, drawn from the seed, is
checked against the plain float32 reference (``bench/reference.py``).
The numbers compared are printed on stderr as the last lines, and in the
result line under ``checks``. The result line is the last line of stdout.

Exits non-zero, printing no result, when JAX's devices are not TPUs or
are fewer than the cell asks for.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # run as a script: import the benchmark as the ``bench`` package (its
    # module names must not shadow the standard library's) and the program
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import loads, ops, peaks  # noqa: E402
from bench import trace as tr  # noqa: E402

BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
# device operations only on a TPU: the profiler's Python and host tracers
# record every Python call and each chunk's host copy, and slow the
# serving loop several times over, so a traced stretch would describe the
# profiler. The CPU's operations are host events (the tests' runs).
TRACER_LEVELS = {"tpu": {"python_tracer_level": 0, "host_tracer_level": 0},
                 "cpu": {"python_tracer_level": 0, "host_tracer_level": 2}}
TRACE_SLICE_S = 3.0     # traffic traced after a --trace 1 window


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _merge(base: dict, extra: dict | None) -> dict:
    out = dict(base)
    out.update(extra or {})
    return out


def load_cell(workload: str, root: Path = ROOT,
              overrides: dict | None = None) -> dict:
    """Everything one cell needs, found by its name."""
    overrides = overrides or {}
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(root / conf["file"]) as f:
        cfg = _merge(json.load(f), overrides.get("config"))
    with open(root / "bench" / "workloads" / f"{workload}.json") as f:
        cell = _merge(json.load(f), overrides.get("cell"))
    traffic = loads.Traffic.load(entry["traffic"], root / "bench")
    if overrides.get("traffic"):
        traffic = loads.Traffic(**_merge(traffic.__dict__,
                                         overrides["traffic"]))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"name": workload, "chips": int(entry["chips"]), "cfg": cfg,
            "cell": cell, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def devices_for(chips: int, require_tpu: bool = True) -> list:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU: JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def arch_config(cfg: dict):
    """The program's configuration object for a configuration file."""
    from repro.configs.base import ArchConfig
    b = cfg["backends"]
    return ArchConfig(
        name=cfg["name"], family="vit", n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        kv_heads=cfg["n_heads"], d_ff=cfg["d_ff"], vocab=0,
        img_size=cfg["img_size"], patch=cfg["patch"],
        quant_bits=cfg["quant_bits"], mgnet=True,
        mgnet_embed=cfg["mgnet_embed"], mgnet_heads=cfg["mgnet_heads"],
        norm_eps=cfg["norm_eps"], remat=False,
        matmul_backend=b["matmul"], attn_backend=b["attn"],
        ffn_backend=b["ffn"])


def _traced_slice(server, streams: list, t: loads.Traffic, seed: int,
                  platform: str) -> loads.Served:
    """A short stretch of the same traffic, after the window, under the
    profiler, which takes seconds to stop and so stays out of the
    measured window."""
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    for k, v in TRACER_LEVELS[platform].items():
        setattr(opts, k, v)
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        if t.loop == "closed":
            traced = loads.closed_loop(server, streams, t, TRACE_SLICE_S,
                                       first=t.frames_per_session)
        else:
            traced = loads.open_loop(server, streams, t, TRACE_SLICE_S,
                                     seed)
    jax.profiler.stop_trace()
    return traced


def _window_work(cfg: dict, served: loads.Served) -> ops.Work:
    work = ops.Work()
    work.merge(ops.embed_work(cfg, served.frames))
    work.merge(ops.mgnet_work(cfg, served.scored))
    for k, n_real in served.flushes:
        work.merge(ops.encode_work(cfg, k, n_real))
    return work


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


_COMPILE_EVENTS = ("jaxpr_trace_duration", "backend_compile_duration",
                   "cache_retrieval")


@contextlib.contextmanager
def _compile_events():
    """[count, seconds] of JAX's tracing and compiling (a compile served
    from the persistent cache included) inside the block: none belongs in
    a measured window."""
    import jax
    seen = [0, 0.0]

    def listen(event: str, duration: float, **_):
        if any(k in event for k in _COMPILE_EVENTS):
            seen[0] += 1
            seen[1] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@contextlib.contextmanager
def _gc_pauses():
    """[count, full collections, longest pause in s] of Python's garbage
    collector inside the block: a host stall in the window is named by
    it or ruled out."""
    seen = [0, 0, 0.0]
    began = [0.0]

    def listen(phase: str, info: dict):
        if phase == "start":
            began[0] = time.perf_counter()
            return
        seen[0] += 1
        seen[1] += info.get("generation") == 2
        seen[2] = max(seen[2], time.perf_counter() - began[0])

    gc.callbacks.append(listen)
    try:
        yield seen
    finally:
        gc.callbacks.remove(listen)


def _p95(xs: list) -> float:
    return statistics.quantiles(xs, n=20, method="inclusive")[18]


def end_to_end(traffic: loads.Traffic, served: loads.Served,
               setup_s: float) -> dict:
    out = {"setup_s": setup_s}
    if traffic.loop == "closed":
        out["frames_per_s"] = served.frames / served.window_s
    else:
        out["clip_latency_p50_ms"] = 1e3 * statistics.median(
            served.clip_latency_s)
    return out


def check(c: dict, served: loads.Served, streams: list, raw_params,
          seed: int) -> dict:
    """The numbers compared, each with its limit."""
    from bench import reference
    cell, cfg, t = c["cell"], c["cfg"], c["traffic"]
    keep = int(cell["keep_patches"])
    frames = [(s, fi) for s, preds in enumerate(served.predictions)
              for fi in preds]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 11]))
    pick = rng.choice(len(frames), size=min(len(frames),
                                            int(cell["sample_frames"])),
                      replace=False)
    images, score_images, classes = [], [], []
    walks: dict = {}
    for i in sorted(pick):
        s, fi = frames[i]
        cam, start, n = served.sessions[s]
        if s not in walks:
            walks[s] = reference.scoring_frames(
                streams[cam].frames(start, n),
                int(cell["server"]["mask_refresh"]),
                float(cell["server"]["delta_threshold"]))
        images.append(streams[cam].frames(fi, 1)[0])
        score_images.append(
            streams[cam].frames(start + int(walks[s][fi - start]), 1)[0])
        classes.append(served.predictions[s][fi])
    model = reference.ReferenceModel(raw_params, cfg, keep)
    ref = model.logits(np.stack(images), np.stack(score_images))
    gaps = reference.served_gaps(ref, classes)
    lim = cell["limits"]
    return {
        "frames_missing": {"value": sum(served.sessions[s][2]
                                        - len(served.predictions[s])
                                        for s in served.failed),
                           "limit": 0},
        "flushes_off_bucket": {"value": sum(k != keep
                                            for k, _ in served.flushes),
                               "limit": 0},
        "max_gap": {"value": float(gaps.max()), "limit": lim["max_gap"]},
        "mean_gap": {"value": float(gaps.mean()), "limit": lim["mean_gap"]},
    }


def setup_cell(c: dict, seed: int, require_tpu: bool = True,
               server_hook=None) -> dict:
    """Weights from the seed, the server with the cell's one bucket
    compiled, the cameras' frames, and one untimed round of the traffic.
    ``server_hook``, when given, is called with the built server (the
    tests use it to break the timed path underneath)."""
    devs = devices_for(c["chips"], require_tpu)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.server import ServerConfig, StreamServer

    from bench import reference
    if devs[0].platform == "tpu":
        # every program, small ones too, so that a second run compiles
        # nothing
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    cfg, t, cell = c["cfg"], c["traffic"], c["cell"]
    raw = reference.make_init(cfg, seed)
    server = StreamServer(
        arch_config(cfg),
        ServerConfig(warm_start=False,
                     mesh="auto" if c["chips"] > 1 else "off",
                     **cell["server"]),
        params=raw, n_classes=cfg["n_classes"], seed=0)
    if server_hook is not None:
        server_hook(server)
    server.warm_start(buckets=(int(cell["keep_patches"]),))
    streams = [loads.RingStream(loads.render_ring(
        t, cfg["img_size"], cfg["patch"], seed, cam))
        for cam in range(t.cameras)]
    # every host path and device program the window uses runs once here
    loads.serve_round(server, [(cam, st, 0, t.frames_per_session)
                               for cam, st in enumerate(streams)],
                      loads.Served())
    return {"devs": devs, "server": server, "streams": streams, "raw": raw}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, overrides: dict | None = None,
             require_tpu: bool = True, server_hook=None) -> dict:
    """One run of a cell; returns the result object."""
    t_setup = time.perf_counter()
    c = load_cell(workload, root, overrides)
    st = setup_cell(c, seed, require_tpu, server_hook)
    setup_s = time.perf_counter() - t_setup
    devs, server, streams, raw = (st["devs"], st["server"], st["streams"],
                                  st["raw"])
    cfg, t, cell = c["cfg"], c["traffic"], c["cell"]

    with _compile_events() as compiles, _gc_pauses() as pauses:
        if t.loop == "closed":
            # set-up served each camera's first session
            served = loads.closed_loop(server, streams, t, seconds,
                                       first=t.frames_per_session)
        else:
            served = loads.open_loop(server, streams, t, seconds, seed)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}

    metrics = {}
    breakdown = None
    if trace:
        traced = _traced_slice(server, streams, t, seed, devs[0].platform)
        red = tr.reduce(tr.load(TRACE_DIR), n_devices=len(devs),
                        window_s=traced.window_s)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        ctx = {"served": served, "traced": traced, "cfg": cfg,
               "traffic": t, "cell": cell, "chips": len(devs), "trace": red,
               "work": _window_work(cfg, traced),
               "window_work": _window_work(cfg, served),
               "peaks": (peaks.peaks_for(devs[0].device_kind)
                         if require_tpu else None)}
        for m in c["per_layer"]:
            v = _reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(t, served, setup_s)
        for m in c["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    del server, st
    gc.collect()
    checks = check(c, served, streams, raw, seed)
    attempted = sum(n for _, _, n in served.sessions)
    failed = checks["frames_missing"]["value"]
    out = {"correct": all(v["value"] <= v["limit"]
                          for v in checks.values()),
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    if served.late_s:
        print(f"generator lateness: median "
              f"{float(statistics.median(served.late_s))!r} s, max "
              f"{float(max(served.late_s))!r} s over {len(served.late_s)} "
              f"calls", file=sys.stderr)
    print(f"window {served.window_s!r} s, {served.calls} serve calls, "
          f"{served.frames} frames ({served.frames / served.window_s!r}/s), "
          f"{served.scored} scored, {len(served.flushes)} encode launches",
          file=sys.stderr)
    print(f"tracing and compiling inside the window: {compiles[0]} events, "
          f"{compiles[1]!r} s", file=sys.stderr)
    if served.clip_latency_s:
        lat = [float(x) for x in served.clip_latency_s]
        print(f"clip latency: median {1e3 * statistics.median(lat)!r} ms, "
              f"p95 {1e3 * _p95(lat)!r} ms over "
              f"{len(served.clip_latency_s)} clips", file=sys.stderr)
    print(f"garbage collection inside the window: {pauses[0]} "
          f"collections ({pauses[1]} full), longest {pauses[2]!r} s",
          file=sys.stderr)
    if trace:
        print(f"traced stretch {traced.window_s!r} s, {traced.frames} "
              f"frames ({traced.frames / traced.window_s!r}/s)",
              file=sys.stderr)
    for name, v in checks.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
