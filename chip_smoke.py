"""Smoke run of the streaming serving path on a TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chip         # a four-chip host

One chip: builds ``StreamServer`` as ``repro.serving.server.main`` does
for Opto-ViT-Base at 224 px (12 layers, d=768, 12 heads, d_ff=3072) on the
fused serving triple ``photonic_pallas`` + ``flash`` + ``fused``, with
random weights from ``--seed``, warms its jit ladder, and serves two
synthetic camera streams of 32 frames each. It fails when:

  * JAX's first device is not a TPU (it never falls back to the CPU);
  * a session comes back poisoned or short of frames;
  * a fused-path fallback warning fires (escalated to an error);
  * the compiled encode lacks the ``photonic_matmul``,
    ``flash_attention_masked`` or ``fused_ffn`` Pallas kernels;
  * the served logits of one bucket disagree with the composed oracle
    (``photonic_sim`` matmuls, ``xla`` attention, ``xla`` FFN, float32
    matmul precision) run on the same gathered tokens, flush by flush.

The oracle tolerance is the README's one-quant-step policy applied per
requantizing layer: every logit must sit within ``n_layers`` 8-bit steps
of the oracle's per-tensor logit range (``n_layers * max|logit| / 127``).
The int32 accumulates of both paths are exact; what differs is float
rounding (streaming vs materialized softmax, the kernels' in-kernel
dequant, the chip's matmul precision), which can move an activation code
by one step at a rounding boundary in each of the encoder's layers, and
the next layers carry it on. Argmax must match on every row whose oracle
top-2 margin exceeds twice the tolerance, where an error within it cannot
reorder the classes; near-tie rows are counted and printed, not judged.

``--four-chip`` serves the same two streams three ways and nothing else:
the default data-parallel mesh over all devices, the 2x2 model-sharded
``shard_map`` encode (``model_shards=2``), and one device with the mesh
off. Predictions must be equal across the three, the sharded encoder must
have engaged, and the prepared int8 weights and the encode outputs must
span every device.

Progress goes to stdout; the last line is one JSON object with the device
as JAX reports it. The frames/s printed is a wall-clock smoke figure that
includes host scheduling, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FRAMES = 32                 # per session
SESSIONS = 2


def _serving_cfg():
    from repro.configs.opto_vit import get_config
    return get_config("base", img_size=224, mgnet=True).with_(
        matmul_backend="photonic_pallas", attn_backend="flash",
        ffn_backend="fused")


def _spy_encodes(server) -> list:
    """Wrap the server's encode jits so every served flush's
    (bucket, tokens, logits) is kept; the serve loop is unchanged."""
    seen: list = []

    def wrap(fn, k):
        def run(params, tokens, *rest):
            out = fn(params, tokens, *rest)
            seen.append((k, tokens, out))
            return out
        return run

    server._encode = wrap(server._encode, None)
    server._encode_one = {k: wrap(f, k)
                          for k, f in server._encode_one.items()}
    return seen


def _serve(server, seed: int) -> dict:
    """Serve the smoke streams; returns {sid: predictions}."""
    from repro.data.pipeline import video_fleet
    sessions = [server.add_session(st, n_frames=FRAMES)
                for st in video_fleet(SESSIONS, img_size=server.cfg.img_size,
                                      patch=server.cfg.patch, seed=seed)]
    t0 = time.time()
    results = server.serve()
    wall = time.time() - t0
    served = 0
    for s in sessions:
        r = results[s.sid]
        if r.poisoned:
            raise RuntimeError(f"session {s.sid} poisoned: {r.failure}")
        if r.frames != FRAMES or len(r.predictions) != FRAMES:
            raise RuntimeError(f"session {s.sid} served {r.frames} of "
                               f"{FRAMES} frames")
        served += r.frames
    print(f"frames served: {served} in {wall:.3f} s wall")
    print(f"frames/s: {served / wall:.1f} (wall clock, smoke figure, "
          f"not a benchmark)")
    return {s.sid: list(results[s.sid].predictions) for s in sessions}


def _check_oracle(server, cfg, enc, flushes) -> None:
    import jax
    import numpy as np
    from repro.core.backend import ExecPolicy
    from repro.models.vit import forward_vit_tokens
    from repro.roofline.hlo_analysis import tpu_kernel_counts

    by_bucket: dict[int, list] = {}
    for _, tokens, logits in flushes:
        by_bucket.setdefault(tokens.shape[1], []).append((tokens, logits))
    k = max(by_bucket, key=lambda b: len(by_bucket[b]))
    flushes = by_bucket[k]
    kernels = tpu_kernel_counts(
        enc.lower(server.params, flushes[0][0]).compile().as_text())
    print(f"compiled encode kernels (bucket {k}): {kernels}")
    missing = [n for n in ("photonic_matmul", "flash_attention_masked",
                           "fused_ffn") if not kernels.get(n)]
    if missing:
        raise RuntimeError(f"compiled encode lacks Pallas kernels {missing}")

    ocfg = cfg.with_(matmul_backend="photonic_sim", attn_backend="xla",
                     ffn_backend="xla")
    opol = ExecPolicy.from_cfg(ocfg, training=False)
    oracle = jax.jit(lambda p, t: forward_vit_tokens(p, t, ocfg, opol)[0])
    got, ref = [], []
    for tokens, logits in flushes:      # one launch each: same absmax scope
        with jax.default_matmul_precision("highest"):
            r = oracle(server.params, tokens)
        real = np.abs(np.asarray(tokens)).sum(axis=(1, 2)) > 0
        got.append(np.asarray(logits)[real])
        ref.append(np.asarray(r)[real])
    got, ref = np.concatenate(got), np.concatenate(ref)
    if not np.isfinite(got).all():
        raise RuntimeError("served logits are not finite")
    tol = cfg.n_layers * np.abs(ref).max() / 127.0
    err = np.abs(got - ref).max()
    top2 = np.sort(ref, axis=-1)[:, -2:]
    decisive = top2[:, 1] - top2[:, 0] > 2 * tol
    same = got.argmax(-1) == ref.argmax(-1)
    corr = np.corrcoef(got.ravel(), ref.ravel())[0, 1]
    print(f"oracle: bucket {k}, {len(flushes)} flushes, {len(ref)} rows; "
          f"max |served - oracle| = {err!r}, tolerance {tol!r} "
          f"({cfg.n_layers} quant steps), corr {corr!r}; argmax equal on "
          f"{int(same[decisive].sum())}/{int(decisive.sum())} decisive "
          f"rows and {int(same[~decisive].sum())}/{int((~decisive).sum())} "
          f"near-tie rows")
    if err > tol:
        raise RuntimeError(f"served logits off the oracle by {err!r} > "
                           f"{tol!r}")
    if not decisive.any() or not same[decisive].all():
        raise RuntimeError("served argmax differs from the oracle on a "
                           "decisive row (or no row was decisive)")


def one_chip(seed: int) -> None:
    from repro.serving.server import ServerConfig, StreamServer
    cfg = _serving_cfg()
    server = StreamServer(cfg, ServerConfig(warm_start=False), seed=seed)
    print(f"server: {cfg.name} {cfg.img_size}px policy {server.policy!r} "
          f"ladder {list(server.ladder.sizes)}")
    server.warm_start()
    print(f"warm-up: {server.warm_s:.3f} s")
    enc = server._encode
    seen = _spy_encodes(server)
    _serve(server, seed)
    _check_oracle(server, cfg, enc, seen)


def four_chip(seed: int) -> None:
    import jax
    from repro.core.backend import QuantizedWeight
    from repro.models.sharded_encoder import sharded_encoder_cache_size
    from repro.serving.server import ServerConfig, StreamServer

    n = len(jax.devices())
    if n != 4:
        raise RuntimeError(f"--four-chip needs 4 devices, JAX sees {n}")
    cfg = _serving_cfg()
    preds = {}
    for name, kw in (("data-parallel", dict(mesh="auto")),
                     ("model-sharded 2x2", dict(mesh="auto",
                                                model_shards=2)),
                     ("one device", dict(mesh="off"))):
        server = StreamServer(cfg, ServerConfig(warm_start=False,
                                                one_shape=True, **kw),
                              seed=seed)
        mesh = ("off" if server.mesh is None else
                "x".join(map(str, server.mesh.devices.shape)))
        server.warm_start()
        print(f"{name}: mesh {mesh}, warm-up {server.warm_s:.3f} s")
        seen = _spy_encodes(server)
        preds[name] = _serve(server, seed)
        if server.mesh is None:
            continue
        wq = server.params["blocks"]["attn"]["wq"]
        assert isinstance(wq, QuantizedWeight), type(wq)
        spans = {"weights": len(wq.wq.sharding.device_set),
                 "encode output": len(seen[0][2].sharding.device_set)}
        print(f"{name}: devices spanned {spans}")
        if min(spans.values()) != n:
            raise RuntimeError(f"{name} does not span all {n} devices: "
                               f"{spans}")
    if sharded_encoder_cache_size() == 0:
        raise RuntimeError("the model-sharded encoder never engaged")
    ref = preds["one device"]
    for name, p in preds.items():
        diff = sum(a != b for sid in ref for a, b in zip(p[sid], ref[sid]))
        if diff:
            raise RuntimeError(f"{name} predictions differ from one device "
                               f"on {diff} of {SESSIONS * FRAMES} frames")
    print(f"predictions equal across {sorted(preds)}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and camera streams")
    ap.add_argument("--four-chip", action="store_true",
                    help="compare data-parallel, 2x2 model-sharded and "
                         "one-device serving on a four-chip host")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU: JAX found platform "
                         f"{dev.platform!r} ({dev.device_kind})")
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {dev.device_kind} x{len(jax.devices())}, compile cache "
          f"{enable_compile_cache()}")
    warnings.filterwarnings("error", category=UserWarning,
                            message=r"fused .* fell back")
    if args.four_chip:
        four_chip(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
