"""Multi-stream session server: many cameras, one photonic accelerator.

The paper's deployment target is a *fleet* of near-sensor streams, and the
throughput lever that Lightening-Transformer / ViTA both lean on is keeping
the accelerator array saturated across concurrent workloads. This module
multiplexes any number of ``StreamSession``\\ s (per-stream state:
``repro.serving.session``) over one shared ``StreamServer`` that owns every
resource the single-stream engine used to conflate with stream state:

  * **one prepared parameter set** — ``prepare_params`` (MR tuning) runs
    once per server, not once per stream;
  * **one per-bucket jit ladder, warmed eagerly at startup** —
    ``warm_start()`` compiles embed/score/order/gather and every bucket's
    encode before the first frame arrives, so first-flush compiles are a
    startup cost instead of being charged to some unlucky stream's fps;
  * **one cross-stream ``MicroBatcher``** — every session's routed frame
    groups land in the same scheduler, keyed ``(bucket, session)``; each
    scheduling round serves sessions in rotating round-robin order and
    executes ready flushes interleaved one-per-session (per-session
    fairness: a bursty stream's backlog cannot starve the others), with an
    optional ``max_wait_chunks`` deadline that pad-flushes partially
    filled micro-batches (``MicroBatcher.flush_stale``);
  * **the device mesh** — with more than one visible device, flushed
    (microbatch, k, d) encodes are placed with the existing ``"batch"``
    logical axis over a 1-D ``("data",)`` mesh (``launch.mesh.
    make_serving_mesh`` + ``distributed.sharding.DATA_RULES``), so the
    batch axis data-parallelizes with zero model-code changes.

**Why micro-batches are session-pure by default.** Every w8a8 backend
quantizes activations with a *per-launch, per-tensor* absmax
(``core/backend._photonic_prologue``), so all frames sharing an encode
launch share quantization scales: co-batching frames from different streams
would couple their numerics (stream A's predictions would depend on what
stream B happened to be looking at). Keyed ``(bucket, session)``, the
shared scheduler multiplexes *launch order* across streams while each
launch's absmax scope stays one stream — which is exactly what makes
round-robin interleaved serving bit-identical, per stream, to sequential
single-stream runs on every backend (enforced by tests/test_multistream.py).
``mix_streams=True`` opts into genuinely cross-session filling (maximum
saturation at partial ladder occupancy) and trades that reproducibility
away on quantized backends; zero padding is always safe — zeros never raise
an absmax.

CLI (4 interleaved streams on the fully fused Pallas path):

    PYTHONPATH=src python -m repro.serving.server --smoke --streams 4 \\
        --backend photonic_pallas --attn-backend flash --ffn-backend fused
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import time
import warnings
from dataclasses import dataclass, fields as _dc_fields

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint.checkpoint import latest_step, restore_flat
from repro.checkpoint.checkpoint import save as _ckpt_save
from repro.configs.base import ArchConfig
from repro.core.backend import (ExecPolicy, available_backends,
                                prepare_params)
from repro.core.mgnet import MGNetConfig, mask_budget, mgnet_scores
from repro.core.noise import DriftState, NoiseSpec
from repro.core.noise import scoped as _noise_scoped
from repro.data.pipeline import VideoStream, video_fleet
from repro.distributed.fault_tolerance import StragglerDetector
from repro.distributed.sharding import (ShardingCtx, named_sharding,
                                        rules_for_mesh, use_sharding)
from repro.launch.mesh import make_serving_mesh
from repro.models.vit import (embed_patches, forward_vit_masked,
                              forward_vit_tokens, init_vit)
from repro.serving.buckets import BucketLadder
from repro.serving.faults import (CheckpointFault, FatalFault, FaultInjector,
                                  FaultSpec, ServeError, ServerCrash,
                                  SessionFailure, TransientFault)
from repro.serving.mask_cache import TemporalMaskCache
from repro.serving.scheduler import MicroBatcher
from repro.serving.session import (ServingConfig, StreamResult,
                                   StreamSession)
from repro.serving.spans import Spans

__all__ = ["ServerConfig", "StreamServer", "interleave_rounds", "main"]


def _gather_topk_rows(tokens, order, keep: int):
    """(C, N, d) tokens + (C, N) descending score order -> (C, keep, d).

    The top-``keep`` prefix of the shared order is exactly what
    ``select_topk_patches`` would select (same stable argsort), without
    re-sorting per bucket.
    """
    return jnp.take_along_axis(tokens, order[:, :keep, None], axis=1)


def _whole_on_each_device(fn, mesh):
    """``fn`` computed whole on every device of ``mesh``, its inputs and
    outputs replicated, under ``shard_map`` — the form in which a TPU runs
    the Pallas kernels of the (small) embed and RoI-gate programs on a
    multi-chip mesh (XLA cannot partition them). Each device computes
    exactly the one-device program, so results stay bitwise equal to it.
    ``fn`` itself without a mesh."""
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def interleave_rounds(groups, depth: int = 1) -> list:
    """Round-robin merge: ``depth`` elements from each list per pass.

    [[a1, a2, a3], [b1]] -> [a1, b1, a2, a3] at depth 1 — the fairness
    order for executing ready flushes: a session with a backlog yields
    after every ``depth`` launches to every other session that has one
    ready. Depth > 1 (the controller's ``interleave_depth`` knob) trades
    a little per-session fairness for fewer rotation passes when every
    session has a deep ready backlog.
    """
    if depth < 1:
        raise ValueError("interleave depth must be >= 1")
    out, i = [], 0
    while True:
        row = [x for g in groups for x in g[i: i + depth]]
        if not row:
            return out
        out.extend(row)
        i += depth


@dataclass(frozen=True)
class ServerConfig(ServingConfig):
    """ServingConfig + the multi-stream knobs."""

    max_wait_chunks: int = 0     # > 0: pad-flush a partial micro-batch after
    #                              this many scheduling rounds (latency bound;
    #                              0 keeps frames queued until the bucket
    #                              fills or the stream ends — the bitwise-
    #                              reproducible default)
    mix_streams: bool = False    # fill one bucket's micro-batch from several
    #                              sessions (max saturation; couples w8a8
    #                              activation scales across streams — see
    #                              module docstring)
    warm_start: bool = True      # compile the whole jit ladder at startup
    mesh: str = "auto"           # "auto": shard the encode batch axis over a
    #                              1-D data mesh when > 1 device is visible;
    #                              "off": never
    model_shards: int = 0        # > 1: 2-D ("data", "model") serving mesh —
    #                              attention heads + d_ff shard over "model"
    #                              (MODEL_RULES), the fused encode runs under
    #                              shard_map (models/sharded_encoder.py),
    #                              bitwise-equal to unsharded. 0/1 = batch-only
    bit_plan: tuple = ()         # mixed-precision bit plan for the shared
    #                              weight cache (per-layer tuple or the dict
    #                              form — core/bitalloc.py); () = uniform
    #                              quant_bits. ``--bit-budget`` instead
    #                              calibrates one at startup
    autotune: bool = False       # serving control plane: route-probe the
    #                              ladder, price hit buckets with the HLO
    #                              cost model (the compiles double as AOT
    #                              encode executables), then run the online
    #                              controller (serving/control/)
    retune_every: int = 32       # frames between controller evaluations
    interleave_depth: int = 1    # default ready-flush launches per session
    #                              per rotation pass (the controller's
    #                              tunable counterpart)
    telemetry_window: int = 256  # flush-observation ring-buffer size
    faults: FaultSpec | None = None  # deterministic fault injection
    #                              (serving/faults.py); None keeps the loop
    #                              on the exact fault-free instruction
    #                              stream — zero overhead, zero RNG
    retry_limit: int = 3         # transient-fault retries per flush before
    #                              the owning session is quarantined
    retry_backoff_s: float = 0.002  # base of the bounded exponential
    #                              backoff between flush retries (doubles
    #                              per attempt, capped at 1s; 0 disables)
    watchdog: bool = False       # time every flush (block_until_ready —
    #                              costs the async overlap, like autotune)
    #                              and feed a StragglerDetector through the
    #                              telemetry ring: anomalously slow flushes
    #                              land in ``server.straggler_flags``
    max_pending_rows: int = 0    # > 0: bound the shared batcher; an ingest
    #                              chunk arriving above the bound is load-
    #                              shed (dropped, counted per session) —
    #                              the overload response that keeps queue
    #                              memory and latency bounded
    checkpoint_dir: str = ""     # root for periodic serve-loop snapshots
    checkpoint_every: int = 0    # > 0: checkpoint every N scheduling
    #                              rounds (needs checkpoint_dir)
    checkpoint_keep: int = 3     # newest snapshots retained per root

    @staticmethod
    def from_serving(sc: ServingConfig, **overrides) -> "ServerConfig":
        """ServerConfig carrying ``sc``'s fields plus ``overrides``. An
        ``sc`` that already is a ServerConfig keeps its server-specific
        knobs (deadline, mixing, mesh) — only the overrides change."""
        src = type(sc) if isinstance(sc, ServerConfig) else ServingConfig
        base = {f.name: getattr(sc, f.name) for f in _dc_fields(src)}
        base.update(overrides)
        return ServerConfig(**base)


class StreamServer:
    """Shared serving resources + the multi-stream scheduling loop."""

    def __init__(self, cfg: ArchConfig, server_cfg: ServerConfig | None = None,
                 params: dict | None = None, n_classes: int = 10,
                 seed: int = 0):
        if not cfg.mgnet:
            raise ValueError("serving engine needs cfg.mgnet=True "
                             "(the RoI gate is the pipeline's first stage)")
        self.cfg = cfg
        self.serve_cfg = server_cfg or ServerConfig()
        self.policy = ExecPolicy.from_cfg(cfg, training=False)
        # calibrated device noise (cfg.noise: core/noise.py NoiseSpec).
        # The DriftState is server-owned — one device, one thermal history
        # shared by every stream — and is threaded through the jit ladder
        # as an explicit traced argument (``_nargs``), so its per-flush
        # evolution never retraces anything.
        self.noise: NoiseSpec | None = getattr(cfg, "noise", None)
        self.drift = (DriftState.init(self.noise.seed)
                      if self.noise is not None else None)
        self._host_drift_nm = 0.0
        self.recalibrations = 0
        self._active_plan = None
        self.n_patches = (cfg.img_size // cfg.patch) ** 2
        self.ladder = BucketLadder.from_fractions(
            self.n_patches, self.serve_cfg.bucket_fractions)
        self.mcfg = MGNetConfig(patch=cfg.patch, img_size=cfg.img_size,
                                embed=cfg.mgnet_embed, heads=cfg.mgnet_heads)

        if params is None:
            params = init_vit(jax.random.PRNGKey(seed), cfg, n_classes)
        # the raw (pre-tuning) weights are kept: ``calibrate_bits`` re-tunes
        # the cache from them under the emitted plan
        self._raw_params = params
        self.layer_bits: tuple | None = None
        # control plane (populated by autotune_prepare): AOT executables
        # from the cost model's compiles, keyed by bucket
        self._encode_aot: dict[int, object] = {}
        self.cost_model = None
        self.telemetry = None
        self.controller = None
        if self.policy.is_photonic():
            # MR tuning happens once, before any stream starts — shared by
            # every session the server will ever serve.
            params = self._prepare(self.serve_cfg.bit_plan
                                   or getattr(cfg, "bit_plan", None) or None)
        self.params = params

        self.mesh = (make_serving_mesh(
                         model=max(1, self.serve_cfg.model_shards))
                     if self.serve_cfg.mesh == "auto" else None)
        self._rules = rules_for_mesh(self.mesh)
        self._ctx = (ShardingCtx(self.mesh, self._rules)
                     if self.mesh is not None else None)
        self.params = self._maybe_place(self.params)

        cfg_, pol = cfg, self.policy
        gpol = pol.gate_policy()
        whole = functools.partial(_whole_on_each_device, mesh=self.mesh)
        # named, so that each program's XLA module reads jit_<name> in a
        # profiler trace
        if self.noise is None:
            def opto_embed(p, f):
                return embed_patches(p, f, cfg_, pol)

            def opto_encode(p, t):
                return forward_vit_tokens(p, t, cfg_, pol)[0]

            def opto_encode_dense(p, f, m):
                return forward_vit_masked(p, f, m, cfg_, pol)[0]
        else:
            # every noisy entry takes the DriftState as one extra traced
            # argument and installs the noise scope INSIDE the traced body
            # (`scoped`): the per-call-site key counter then restarts per
            # trace, so retraces, eager replays and cached executions all
            # assign identical keys for equal (params, inputs, DriftState)
            def opto_embed(p, f, ns):
                return _noise_scoped(
                    ns, lambda: embed_patches(p, f, cfg_, pol))

            def opto_encode(p, t, ns):
                return _noise_scoped(
                    ns, lambda: forward_vit_tokens(p, t, cfg_, pol)[0])

            def opto_encode_dense(p, f, m, ns):
                return _noise_scoped(
                    ns, lambda: forward_vit_masked(p, f, m, cfg_, pol)[0])
        self._embed = jax.jit(whole(opto_embed))
        self._encode = jax.jit(opto_encode)
        self._encode_dense = jax.jit(whole(opto_encode_dense))
        if self.noise is not None and self.noise.noisy_gate:
            def mgnet_score(p, f, ns):
                return _noise_scoped(ns, lambda: mgnet_scores(
                    p["mgnet"], f, self.mcfg, gpol))
        else:
            # default: the RoI gate scores clean even under noise (see
            # ExecPolicy.gate_policy) — routing and bucket shapes stay
            # deterministic, so clean-vs-noisy runs compare frame-by-frame
            def mgnet_score(p, f):
                return mgnet_scores(p["mgnet"], f, self.mcfg, gpol)
        self._score = jax.jit(whole(mgnet_score))

        # one stable descending argsort per chunk (the ordering
        # select_topk_patches defines), then per-bucket static slices of it
        # — not a fresh full-chunk sort + gather per unique bucket
        def patch_order(s):
            return jnp.argsort(s, axis=-1, stable=True, descending=True)

        def _gather_k(k: int):
            def gather_topk(tokens, order):
                return _gather_topk_rows(tokens, order, k)
            return jax.jit(gather_topk)

        self._order = jax.jit(patch_order)
        self._gather = {k: _gather_k(k) for k in self.ladder.sizes}
        self._encode_one = {}
        if self.serve_cfg.one_shape:
            def _one(k: int):
                if self.noise is None:
                    def opto_encode_k(p, t):
                        return forward_vit_tokens(p, t, cfg_, pol,
                                                  kv_len=k)[0]
                else:
                    def opto_encode_k(p, t, ns):
                        return _noise_scoped(ns, lambda: forward_vit_tokens(
                            p, t, cfg_, pol, kv_len=k)[0])
                return jax.jit(opto_encode_k)
            self._encode_one = {k: _one(int(k)) for k in self.ladder.sizes}

        self._sessions: list[StreamSession] = []
        self._next_sid = 0
        self.batcher: MicroBatcher | None = None
        self.flush_log: list[tuple] = []   # (owner sids, bucket k, n_real)
        # counters always on, spans off until ``spans.enable()``
        # (serving/spans.py)
        self.spans = Spans()
        self.warm_s = 0.0
        # fault tolerance: the injector exists only under a FaultSpec (the
        # fault-free loop must stay on the pre-fault-layer instruction
        # stream — see tests/test_serving_faults.py's bitwise pin)
        self.faults: FaultSpec | None = self.serve_cfg.faults
        self._injector = (FaultInjector(self.faults)
                          if self.faults is not None else None)
        self._watchdog = bool(self.serve_cfg.watchdog)
        if self._watchdog and not self.serve_cfg.autotune:
            self.telemetry = self._make_telemetry()
        self.checkpoint_failures = 0
        self._inflight: dict | None = None  # paused serve() loop state
        self._resume: tuple | None = None   # (rnd, offset) from a restore
        # autotune mode compiles its own (probed-only) jit set inside
        # autotune_prepare — an eager full-ladder warm-up would pay for
        # exactly the dead-bucket compiles the probe exists to skip
        if self.serve_cfg.warm_start and not self.serve_cfg.autotune:
            self.warm_start()

    def _maybe_place(self, params):
        """Pin the prepared weight cache onto the serving mesh, once: the
        sharded encoder's layout when it will actually engage (model-axis
        shardings on a 2-D mesh), a replica on every device otherwise
        (left on device 0, the weights would be copied to the others on
        every launch). Params fed to the *unsharded* jit must stay
        replicated: a committed model-axis sharding there would make GSPMD
        add collectives to a graph whose bitwise contract assumes none."""
        if self._ctx is None:
            return params
        from repro.core.backend import place_params
        from repro.models import sharded_encoder, vit
        if (vit._fused_encoder_ineligible_reason(
                params, self.cfg, self.policy) is None
                and sharded_encoder.sharded_encode_ineligible_reason(
                    params, self.cfg, self.policy, self._ctx) is None):
            return place_params(params, vit.vit_logical_axes(self.cfg),
                                self._ctx)
        return jax.device_put(params, NamedSharding(self.mesh, P()))

    def _prepare(self, plan):
        """MR-tune the shared cache from the raw weights under ``plan``
        (None = uniform ``quant_bits``), fold the plan into the policy
        fingerprint (every policy-keyed jit cache re-keys) and derive the
        per-layer energy view threaded to each session's accounting."""
        from repro.core import bitalloc
        bits = self.cfg.quant_bits or 8
        self._active_plan = plan      # recalibrate() re-tunes under it
        nplan = bitalloc.normalize_bit_plan(plan, self.cfg.n_layers,
                                            default=bits)
        self.policy.bit_plan = bitalloc.plan_key(nplan)
        self.layer_bits = (bitalloc.plan_layer_bits(nplan, self.cfg.n_layers)
                           if nplan is not None else None)
        # AOT executables were lowered against the *previous* params
        # pytree; a re-tuned cache may change avals/treedef, so they are
        # dropped (the jit ladder retraces on its own)
        self._encode_aot = {}
        return prepare_params(self._raw_params, bits=bits, bit_plan=plan,
                              n_layers=self.cfg.n_layers)

    # -- session registry --------------------------------------------------

    def add_session(self, stream: VideoStream, n_frames: int = 64,
                    start: int = 0) -> StreamSession:
        """Register a stream for the next ``serve()``; returns its session."""
        s = StreamSession(self._next_sid, stream, n_frames, start,
                          self.serve_cfg, self.cfg, ladder=self.ladder,
                          layer_bits=self.layer_bits)
        self._next_sid += 1
        self._sessions.append(s)
        return s

    def _score_fn(self, frames):
        if self.noise is not None and self.noise.noisy_gate:
            return self._score(self.params, frames, self.drift)
        return self._score(self.params, frames)

    # -- calibrated device noise + drift-triggered recalibration ----------

    def _nargs(self) -> tuple:
        """Extra trailing args for the embed/encode jits: the DriftState
        under noise, nothing otherwise — call sites stay unforked."""
        return (self.drift,) if self.noise is not None else ()

    # duck-typed hook for EncodeCostModel's builders: the AOT lowering must
    # match the serve-time call signature, extra noise args included
    _encode_extra_args = _nargs

    def inject_drift(self, nm: float) -> None:
        """Add ``nm`` of resonance drift on top of the accumulated state —
        a thermal step/transient for robustness experiments."""
        if self.noise is None:
            raise ValueError("inject_drift needs cfg.noise set")
        self.drift = self.drift.with_drift(
            self.drift.drift_nm + jnp.float32(nm))
        self._host_drift_nm += float(nm)

    def _advance_drift(self, frames: int, extra_sessions=()) -> None:
        if self.noise is None or frames <= 0:
            return
        self.drift = self.drift.advance(self.noise, frames)
        # host-side mirror of the deterministic (rate x frames) component:
        # the per-flush bound check must not sync the device
        self._host_drift_nm += frames * self.noise.drift_rate_nm
        if (self.noise.recal_bound_nm > 0.0
                and self._host_drift_nm >= self.noise.recal_bound_nm):
            self.recalibrate(extra_sessions)

    def recalibrate(self, extra_sessions=()) -> None:
        """Online MR re-tuning: re-run the quantize-once ``prepare_params``
        cache from the raw weights under the active plan and zero the
        accumulated drift — the software analogue of re-locking every MR
        bank onto its wavelength. Billed to every live session's energy
        accounting as one full-model tuning pass."""
        if self.policy.is_photonic():
            aot = self._encode_aot
            self.params = self._maybe_place(self._prepare(self._active_plan))
            # same raw weights + same plan -> identical codes, avals and
            # treedef: the cost model's AOT executables stay valid (unlike
            # calibrate_bits, which changes the plan and must drop them)
            self._encode_aot = aot
        if self.drift is not None:
            self.drift = self.drift.reset_drift()
        self._host_drift_nm = 0.0
        self.recalibrations += 1
        for s in list(self._sessions) + list(extra_sessions):
            if not s.finished:
                s.acct.add_recalibration()

    # -- warm-start jit ladder ---------------------------------------------

    def warm_start(self, buckets: tuple | None = None) -> float:
        """Eagerly compile every jit the serving loop can hit — embed,
        score, order, the per-bucket gathers and (by default) every
        bucket's encode at its exact flush shape — so streams never pay a
        compile. ``buckets`` restricts the encode warm-up to a subset of
        ladder sizes (``autotune_prepare`` passes the probe's hit set);
        buckets already backed by an AOT executable from the cost model
        are skipped — their compile already happened. Returns the warm-up
        wall seconds (also kept as ``self.warm_s``)."""
        sc, cfg = self.serve_cfg, self.cfg
        targets = tuple(k for k in self.ladder.sizes
                        if buckets is None or k in buckets)
        t0 = time.time()
        with use_sharding(self.mesh, self._rules):
            zf = jnp.zeros((sc.chunk, cfg.img_size, cfg.img_size, 3),
                           jnp.float32)
            toks = self._embed(self.params, zf, *self._nargs())  # (C, N, d)
            self._score_fn(zf).block_until_ready()
            zs = jnp.asarray(np.zeros((sc.chunk, self.n_patches),
                                      np.float32))
            order = self._order(zs)                        # (C, N) i32
            warm_gathers = ((self.ladder.cap,) if sc.one_shape
                            else targets)
            pruned = {k: self._gather[k](toks, order) for k in warm_gathers}
            for k in targets:
                if k in self._encode_aot:
                    continue
                src = pruned[self.ladder.cap if sc.one_shape else k]
                zt = jnp.zeros((sc.microbatch,) + src.shape[1:], src.dtype)
                zt = self._place(zt)
                enc = (self._encode_one[k] if sc.one_shape else self._encode)
                enc(self.params, zt, *self._nargs()).block_until_ready()
        self.warm_s = time.time() - t0
        return self.warm_s

    # -- dead-bucket trimming ----------------------------------------------

    def trim(self, dead, keep_cap: bool = True) -> tuple[int, ...]:
        """Drop ladder sizes (``StreamAccounting.dead_buckets()`` output)
        and their per-bucket jits; un-started sessions are re-pointed at
        the trimmed ladder. ``keep_cap=False`` lets the ladder cap go too
        — only safe when routing provably cannot exceed the surviving
        sizes (the ``force_bucket`` pin). Returns the sizes removed."""
        new = self.ladder.trim(dead, keep_cap=keep_cap)
        removed = tuple(sorted(set(self.ladder.sizes) - set(new.sizes)))
        self.ladder = new
        for k in removed:
            self._gather.pop(k, None)
            self._encode_one.pop(k, None)
            self._encode_aot.pop(k, None)
        # un-started sessions are replaced, not mutated: their histogram /
        # accounting must key the trimmed ladder (sids are stable, so
        # callers holding the old object still index serve() results)
        self._sessions = [
            s if s.finished or s.frames_seen > 0
            else StreamSession(s.sid, s.stream, s.n_frames, s.start,
                               self.serve_cfg, self.cfg, ladder=self.ladder,
                               layer_bits=self.layer_bits)
            for s in self._sessions]
        return removed

    def _route_probe(self, calib_frames: int | None = None) -> set[int]:
        """Which ladder buckets the registered sessions' leading frames
        route to — host-side scoring only (throwaway mask caches, no
        embeds/encodes, sessions untouched). Under a ``force_bucket`` pin
        the answer is exact by construction: every frame routes to the
        pinned size regardless of content."""
        sc = self.serve_cfg
        if sc.force_bucket > 0:
            return {self.ladder.route(
                int(round(sc.force_bucket * self.n_patches)))}
        calib = calib_frames or 2 * sc.chunk
        calib = ((calib + sc.chunk - 1) // sc.chunk) * sc.chunk
        hit: set[int] = set()
        for s in self._sessions:
            if s.finished:
                continue
            cache = TemporalMaskCache(sc.mask_refresh,
                                      sc.delta_threshold)
            for ofs in range(0, calib, sc.chunk):
                sub = s.stream.frames_at(s.start + ofs, sc.chunk)
                scores, _ = cache.gate(sub["frames"], sub["frame_idx"],
                                       self._score_fn)
                hit |= set(int(k) for k in self.ladder.route_many(
                    mask_budget(scores, self.mcfg.t_reg)))
        return hit

    def calibrate_trim(self, calib_frames: int | None = None
                       ) -> tuple[int, ...]:
        """Route-only calibration pass: score the first ``calib_frames`` of
        every registered session host-side (throwaway mask caches — the
        sessions themselves are untouched and will re-gate from scratch),
        collect which ladder buckets get hit, and ``trim`` the rest. Run
        *before* ``warm_start()`` so the warmed jit set shrinks too.

        Calibration only sees the window it scored: a later budget shift
        (e.g. the first scene cut past ``calib_frames``) whose frames
        would have routed to a trimmed bucket routes up to the next
        surviving size instead — those frames encode more tokens than an
        untrimmed run would, so the interleaved-vs-sequential bitwise
        contract only holds against an equally-trimmed solo server. A
        ``UserWarning`` spells this out whenever something is trimmed;
        size the window past the stream's budget churn (scene-cut period)
        to trim on a representative distribution."""
        sc = self.serve_cfg
        if not any(not s.finished for s in self._sessions):
            # nothing to calibrate against — an empty pass would declare
            # every non-cap bucket dead and collapse the ladder
            return ()
        hit = self._route_probe(calib_frames)
        dead = tuple(k for k in self.ladder.sizes if k not in hit)
        if not dead:
            return ()
        removed = self.trim(dead)
        if removed and sc.force_bucket <= 0:
            warnings.warn(
                f"calibrate_trim dropped buckets {list(removed)} from a "
                f"calibration window the streams may outgrow: budgets that "
                f"later route to a dropped size will route up to the next "
                f"surviving bucket (more tokens, possibly different "
                f"predictions than an untrimmed run)", stacklevel=2)
        return removed

    # -- sensitivity-driven bit allocation ---------------------------------

    def calibrate_bits(self, target_mean_bits: float,
                       calib_frames: int | None = None,
                       candidates: tuple = (6, 4)) -> tuple:
        """Emit a per-layer bit plan meeting ``target_mean_bits`` and
        re-tune the shared weight cache under it (core/bitalloc.py).

        The calibration batch is the first registered unfinished session's
        leading ``calib_frames`` (default one ingest chunk), embedded on
        the server's own policy — the sensitivity ranking then reflects
        the numerics the streams will actually serve at. Re-tuning swaps
        ``self.params`` (treedef change: every params-taking jit retraces
        on its next call) and updates ``policy.bit_plan``; run *before*
        ``warm_start()`` so the warmed jits compile the final plan.
        Un-started sessions are re-pointed so their energy accounting
        carries the plan's per-layer widths. Returns the plan tuple."""
        from repro.core import bitalloc
        if not self.policy.is_photonic():
            raise ValueError("bit allocation needs a photonic backend "
                             "(the plan drives the quantize-once cache)")
        src = next((s for s in self._sessions if not s.finished), None)
        if src is None:
            raise ValueError("register at least one session before "
                             "calibrate_bits (it provides the calibration "
                             "frames)")
        n = calib_frames or self.serve_cfg.chunk
        frames = jnp.asarray(
            src.stream.frames_at(src.start, n)["frames"], jnp.float32)
        # sensitivity calibration runs clean even under noise: the plan
        # should rank layers by their quantization sensitivity, not by one
        # arbitrary noise draw
        cpol = self.policy.without_noise()
        tokens = embed_patches(self.params, frames, self.cfg, cpol)
        plan = bitalloc.calibrate_bit_plan(
            self._raw_params, tokens, self.cfg, cpol,
            target_mean_bits=target_mean_bits, candidates=candidates,
            default=self.cfg.quant_bits or 8)
        self.params = self._maybe_place(self._prepare(plan))
        self._sessions = [
            s if s.finished or s.frames_seen > 0
            else StreamSession(s.sid, s.stream, s.n_frames, s.start,
                               self.serve_cfg, self.cfg, ladder=self.ladder,
                               layer_bits=self.layer_bits)
            for s in self._sessions]
        return plan

    # -- serving control plane ---------------------------------------------

    def autotune_prepare(self, calib_frames: int | None = None):
        """Stand up the serving control plane (``serving/control/``):

        1. **Route probe** — host-side scoring of each session's leading
           frames finds which ladder buckets the workload can hit. Under
           a ``force_bucket`` pin the unreachable sizes are trimmed
           outright (provably route-invariant — every frame routes to the
           pin either way; without ``one_shape`` even the cap can go).
           Otherwise the ladder is left intact: the probe only decides
           which buckets get *compiled*, never where frames route, so
           predictions stay bitwise identical to a statically-knobbed run.
        2. **Cost model** — each probed bucket's encode is lowered,
           compiled and priced (``EncodeCostModel``); off the mesh path
           the compiled executables are installed as the AOT encode set,
           so costing doubled as warm-up and dead buckets never compile.
        3. **Controller** — telemetry ring buffer + the calibrating,
           clamped knob tuner; the serve loop reads ``controller.knobs``
           every round and calls ``controller.step`` every
           ``retune_every`` frames.

        Returns the controller."""
        from repro.serving.control import (Controller, ControllerConfig,
                                           EncodeCostModel, TunedKnobs)
        sc = self.serve_cfg
        probed = self._route_probe(calib_frames)
        if sc.force_bucket > 0:
            dead = tuple(k for k in self.ladder.sizes if k not in probed)
            if dead:
                self.trim(dead, keep_cap=not sc.one_shape)
        self.cost_model = EncodeCostModel.from_server(
            self, buckets=tuple(sorted(probed & set(self.ladder.sizes))))
        if self.mesh is None:
            # the cost model's compiles were cut at the exact flush avals
            # the loop uses — reuse them as the AOT encode path. With a
            # mesh the serve-time shardings differ from the unsharded
            # lowering, so the jit ladder keeps ownership there.
            self._encode_aot = dict(self.cost_model.executables)
        self.warm_start(buckets=tuple(sorted(probed)))
        self.telemetry = self._make_telemetry()
        defaults = TunedKnobs(max_wait_chunks=sc.max_wait_chunks,
                              interleave_depth=sc.interleave_depth)
        self.controller = Controller(
            self.cost_model, self.telemetry, defaults,
            ControllerConfig(retune_every=sc.retune_every))
        return self.controller

    def _make_telemetry(self):
        """Flush-observation ring; with the watchdog on it carries a
        ``StragglerDetector`` so every timed flush feeds the median+MAD
        slow-flush estimate (``straggler_flags``)."""
        from repro.serving.control import FlushTelemetry
        det = StragglerDetector() if self._watchdog else None
        return FlushTelemetry(self.serve_cfg.telemetry_window,
                              straggler=det)

    @property
    def straggler_flags(self) -> list:
        """Flush observations the watchdog flagged as anomalously slow
        (empty without ``watchdog=True`` / ``autotune`` telemetry)."""
        return (list(self.telemetry.straggler_flags)
                if self.telemetry is not None else [])

    # -- the serving loop --------------------------------------------------

    def serve(self, verbose: bool = False,
              max_rounds: int = 0) -> dict[int, StreamResult]:
        """Serve every registered (unfinished) session to completion,
        interleaved round-robin; returns ``{sid: StreamResult}``. Wall
        time is shared: every result's ``wall_s`` is the loop's span, so
        per-session fps reflects multiplexed service and the *aggregate*
        fps is ``sum(frames) / wall``.

        ``max_rounds > 0`` **pauses** after that many scheduling rounds
        and returns ``{}`` with the loop state (sessions, queued rows,
        round/rotation cursors) held in flight — the deterministic stop
        the checkpoint/migration surfaces operate at; calling ``serve()``
        again resumes exactly where it paused.

        Failure semantics (README "Failure semantics & fault injection"):
        transient flush faults retry with bounded exponential backoff;
        fatal/exhausted failures quarantine only the owning session (its
        ``StreamResult`` comes back ``poisoned`` with the reason) while
        every other session serves to completion. Any *unexpected*
        exception still fails the whole serve, but re-raises as a
        ``ServeError`` attributing the failing bucket/sessions/round and
        carrying partial results for sessions that had fully drained."""
        sc = self.serve_cfg
        spans = self.spans
        with spans.span("serve.call"):
            if self._inflight is None:
                live = [s for s in self._sessions if not s.finished]
                if not live:
                    return {}
                for s in live:
                    s.open()
                self.batcher = MicroBatcher(sc.microbatch)
                self.flush_log = []
                rnd, offset = self._resume if self._resume else (0, 0)
                self._resume = None
                st = {"live": live, "rnd": rnd, "offset": offset,
                      "wall_s": 0.0, "retuned_at": 0,
                      "early": self._restore_pending(live)}
                self._inflight = st
            else:
                st = self._inflight
            live = st["live"]
            by_sid = {s.sid: s for s in live}
            t0 = time.time()
            try:
                done = self._serve_loop(st, by_sid, t0, verbose, max_rounds)
            except BaseException as e:
                # an unexpected mid-serve failure poisons the half-served
                # sessions: their accounting/mask-cache state is partial,
                # and re-opening them on the next serve() would re-ingest
                # from frame 0 and double-count — they are abandoned.
                # Sessions that had already fully drained lose nothing:
                # their finished results ride out on the ServeError.
                st["wall_s"] += time.time() - t0
                wall = st["wall_s"]
                partial = {s.sid: s.finish(wall) for s in live
                           if s.drained and (
                               s.failed_reason
                               or s.acct.frames == s.frames_seen)}
                for s in live:
                    s.finished = True
                self._inflight = None
                self._sessions = [s for s in self._sessions
                                  if not s.finished]
                if isinstance(e, ServeError):
                    e.partial_results.update(partial)
                    raise
                ctx = {"round": st["rnd"],
                       "sessions": [s.sid for s in live if not s.drained]}
                raise ServeError(
                    f"serve() died at round {ctx['round']} (sessions "
                    f"{ctx['sessions']} mid-stream): {e}", context=ctx,
                    partial_results=partial) from e
            st["wall_s"] += time.time() - t0
            if not done:
                return {}           # paused by max_rounds; serve() resumes
            wall = st["wall_s"]
            results = {}
            for s in live:
                with spans.span("serve.finish", s.sid):
                    spans.host_syncs += len(s.deferred)
                    results[s.sid] = s.finish(wall)
                spans.end("serve.session", s.sid)
            self._inflight = None
            # finished sessions leave the registry (long-lived servers and
            # the engine shim's run-per-session pattern stay bounded)
            self._sessions = [s for s in self._sessions if not s.finished]
            return results

    def _serve_loop(self, st, by_sid, t0, verbose, max_rounds) -> bool:
        """Run scheduling rounds until every live session drains (returns
        True) or ``max_rounds`` rounds elapse (returns False — paused).
        Cursors (round, rotation offset) persist in ``st`` across pauses
        and checkpoints."""
        sc = self.serve_cfg
        ctl = self.controller
        spans = self.spans
        live = st["live"]
        rounds = 0
        with use_sharding(self.mesh, self._rules):
            early, st["early"] = st.get("early") or [], []
            if early:
                # flushes that became ready while re-queuing a restored
                # checkpoint's pending rows (cannot happen when the
                # snapshot respected the < microbatch queue invariant,
                # but a hand-edited snapshot must not lose frames)
                self._round = st["rnd"]
                for fb in early:
                    self._safe_finish(fb, by_sid)
            while any(not s.drained for s in live):
                if max_rounds and rounds >= max_rounds:
                    return False
                with spans.span("serve.round"):
                    rnd = st["rnd"]
                    # the controller owns the re-timing knobs when
                    # present; kn is re-read every round so a step()
                    # lands immediately
                    kn = ctl.knobs if ctl is not None else None
                    max_wait = (kn.max_wait_chunks if kn is not None
                                else sc.max_wait_chunks)
                    depth = (kn.interleave_depth if kn is not None
                             else sc.interleave_depth)
                    offset = st["offset"]
                    rot = live[offset:] + live[:offset]
                    st["offset"] = (offset + 1) % len(live)
                    per = {s.sid: [] for s in rot}
                    late: list = []
                    for s in rot:
                        if s.ingest_done:
                            continue
                        if (sc.max_pending_rows > 0
                                and self.batcher.pending
                                >= sc.max_pending_rows):
                            # load shedding: the queue bound is hit, so
                            # this chunk is pulled off the sensor and
                            # dropped whole (deferring it would deadlock:
                            # under max_wait=0 a partial queue only fills
                            # from its own session's future ingest)
                            batch = s.next_batch()
                            if batch is not None:
                                s.shed(int((np.asarray(batch["frame_idx"])
                                            < s.limit).sum()))
                            continue
                        if self._injector is not None:
                            # fault check BEFORE next_batch: a raised fault
                            # must never half-consume the prefetch iterator
                            try:
                                self._injector.ingest(
                                    s.sid, s.chunks_done,
                                    attempt=s.ingest_attempts)
                            except TransientFault:
                                s.ingest_attempts += 1
                                s.retries += 1
                                continue    # same chunk retries next round
                            except FatalFault as e:
                                self._fail_sessions((s.sid,), str(e),
                                                    by_sid)
                                continue
                            s.ingest_attempts = 0
                        spans.begin("serve.session", s.sid)
                        with spans.span("serve.ingest", s.sid):
                            batch = s.next_batch()
                        if batch is not None:
                            per[s.sid].extend(
                                self._ingest_chunk(s, batch, rnd))
                    if sc.mix_streams:
                        if all(s.ingest_done for s in live):
                            late.extend(self.batcher.drain())
                            for s in live:
                                s.drained = True
                    else:
                        for s in rot:
                            if s.ingest_done and not s.drained:
                                per[s.sid].extend(self.batcher.drain(
                                    select=lambda key, sid=s.sid:
                                    key[1] == sid))
                                s.drained = True
                    if max_wait > 0:
                        late.extend(
                            self.batcher.flush_stale(rnd - max_wait))
                    if kn is not None and kn.flush_threshold:
                        late.extend(self.batcher.flush_filled(
                            lambda key: kn.flush_threshold.get(
                                key[0] if isinstance(key, tuple) else key,
                                self.batcher.microbatch)))
                    self._round = rnd
                    for fb in interleave_rounds([per[s.sid] for s in rot],
                                                depth):
                        self._safe_finish(fb, by_sid)
                    for fb in late:
                        self._safe_finish(fb, by_sid)
                    st["rnd"] = rnd + 1
                    rounds += 1
                    if self._injector is not None:
                        # may raise ServerCrash
                        self._injector.round_tick(rnd)
                    if (sc.checkpoint_every > 0 and sc.checkpoint_dir
                            and st["rnd"] % sc.checkpoint_every == 0):
                        try:
                            self.checkpoint()
                        except CheckpointFault as e:
                            # checkpoint I/O loss degrades gracefully:
                            # serving continues on the last good snapshot
                            self.checkpoint_failures += 1
                            warnings.warn(f"checkpoint skipped: {e}",
                                          stacklevel=2)
                    if ctl is not None:
                        done = sum(s.acct.frames for s in live)
                        if done - st["retuned_at"] >= sc.retune_every:
                            ctl.step(self.batcher.queue_stats(), done,
                                     time.time() - t0)
                            st["retuned_at"] = done
                    if verbose and st["rnd"] % sc.report_every == 0:
                        dt = time.time() - t0
                        done = sum(s.acct.frames for s in live)
                        print(f"[server] round {st['rnd']:>4d}  "
                              f"{done:>5d} frames  {done / dt:7.1f} "
                              f"frames/s aggregate  "
                              f"(pending {self.batcher.pending}, "
                              f"{sum(not s.ingest_done for s in live)} "
                              f"streams ingesting)")
        if verbose:
            for s in live:
                print(f"[server] session {s.sid}:", s.acct.summary())
        return True

    def _ingest_chunk(self, s: StreamSession, batch: dict, rnd: int) -> list:
        """Gate one session chunk through *its* mask cache, embed on the
        shared jit, route on the shared ladder, and push per-bucket groups
        into the shared batcher. Returns flushes that became ready."""
        sc = self.serve_cfg
        spans = self.spans
        frames = batch["frames"]                           # device view
        idxs = batch["frame_idx"]
        valid = idxs < s.limit
        spans.chunks_ingested += 1
        spans.h2d_bytes += batch["frames_host"].nbytes
        with spans.span("serve.gate", s.sid):
            scores_np, n_scored = s.cache.gate(
                batch["frames_host"], idxs, self._score_fn, eligible=valid)
        if n_scored:
            # one score launch, its scores pulled to the host
            spans.score_launches += 1
            spans.host_syncs += 1
        s.acct.add_mgnet(n_scored)
        with spans.span("serve.route", s.sid):
            toks = self._embed(self.params, frames,
                               *self._nargs())             # (C, N, d)
            # budget decision on host: scores are already host-resident
            # from the mask cache, and mask_budget stays in numpy for them
            if sc.force_bucket > 0:
                pin = self.ladder.route(
                    int(round(sc.force_bucket * self.n_patches)))
                routes = np.full(frames.shape[0], pin)
            else:
                routes = self.ladder.route_many(
                    mask_budget(scores_np, self.mcfg.t_reg))

            spans.h2d_bytes += scores_np.nbytes
            order = self._order(jnp.asarray(scores_np))    # (C, N), shared
            permuted = (self._gather[self.ladder.cap](toks, order)
                        if sc.one_shape else None)         # (C, cap, d)
            out = []
            for k in np.unique(routes[valid]):
                k = int(k)
                sel = np.flatnonzero((routes == k) & valid)
                # one-shape mode ships the shared cap-size permutation and
                # prunes via the static per-bucket kv_len at encode time
                pruned = (permuted if sc.one_shape
                          else self._gather[k](toks, order))  # (C, k, d)
                s.record_route(k, len(sel))
                group = (pruned if len(sel) == frames.shape[0]
                         else pruned[sel])
                key = k if sc.mix_streams else (k, s.sid)
                out.extend(self.batcher.push_many(
                    key, group, [(s.sid, int(idxs[i])) for i in sel],
                    now=rnd))
        s.frames_seen += int(valid.sum())
        return out

    def _place(self, tokens):
        """Shard a flush's batch axis over the data mesh (no-op without)."""
        if self._ctx is None:
            return tokens
        return jax.device_put(tokens, named_sharding(
            tokens.shape, ("batch", None, None), self._ctx))

    def _safe_finish(self, fb, by_sid: dict[int, StreamSession]) -> None:
        """Execute one flush with per-session failure isolation. A
        ``SessionFailure`` (injected fatal fault or exhausted retries)
        quarantines only the owning sessions; any *other* exception means
        the shared serving machinery itself broke, and is re-raised as a
        ``ServeError`` attributing the failing bucket, sessions, frames
        and round — the blanket except that used to lose all of that."""
        owners = sorted({sid for sid, _ in fb.frame_idx})
        if owners and all(by_sid[sid].failed_reason for sid in owners
                          if sid in by_sid):
            return            # stale flush of already-quarantined sessions
        k = fb.bucket[0] if isinstance(fb.bucket, tuple) else fb.bucket
        try:
            self._finish(fb, by_sid)
        except SessionFailure as e:
            self._fail_sessions(e.sids, e.reason, by_sid)
        except ServerCrash:
            raise
        except Exception as e:
            rnd = getattr(self, "_round", 0)
            frames = [f"{sid}:{fi}" for sid, fi in fb.frame_idx]
            raise ServeError(
                f"flush failed at bucket k={k} (sessions {owners}, frames "
                f"{frames}, round {rnd}): {e}",
                context={"bucket": k, "sessions": owners,
                         "n_real": fb.n_real, "round": rnd}) from e

    def _fail_sessions(self, sids, reason: str,
                       by_sid: dict[int, StreamSession]) -> None:
        """Quarantine the named sessions: mark them failed (their
        ``StreamResult`` comes back ``poisoned`` with ``reason``), drop
        their queued-but-unflushed frames so no further launch is billed
        to them, and let every other session keep serving. Session-keyed
        batcher queues make the discard surgical; under ``mix_streams``
        queues are shared, so queued rows stay (their flushes skip the
        failed owners' bookkeeping via ``failed_reason``)."""
        fresh = [sid for sid in sids
                 if sid in by_sid and not by_sid[sid].failed_reason]
        if not fresh:
            return
        for sid in fresh:
            by_sid[sid].fail(reason)
        if not self.serve_cfg.mix_streams:
            doomed = set(fresh)
            self.batcher.discard(
                lambda key: isinstance(key, tuple) and key[1] in doomed)
        warnings.warn(f"quarantined session(s) {fresh}: {reason} — "
                      f"remaining sessions keep serving", stacklevel=3)

    def _finish(self, fb, by_sid: dict[int, StreamSession]) -> None:
        # scheduling round tag rides on an instance field, not a parameter:
        # the signature is a stable seam tests stub out
        rnd = getattr(self, "_round", 0)
        sc = self.serve_cfg
        k = fb.bucket[0] if isinstance(fb.bucket, tuple) else fb.bucket
        inj = self._injector
        tag = fb.frame_idx[0] if fb.frame_idx else (0, 0)
        timed = self.controller is not None or self._watchdog
        owners: dict[int, tuple[list, list]] = {}
        for row, (sid, fidx) in enumerate(fb.frame_idx):
            rows, fidxs = owners.setdefault(sid, ([], []))
            rows.append(row)
            fidxs.append(fidx)
        sids = tuple(sorted(owners))
        spans = self.spans
        # the span's clock times a timed flush: launch to materialized
        # result, failed attempts and their backoff included
        with spans.span("serve.flush", spans.flushes, bucket=k, owners=sids,
                        clock=timed) as sp:
            attempt = 0
            while True:
                try:
                    if inj is not None:
                        inj.flush(k, tag, attempt=attempt)
                    tokens = self._place(fb.tokens)
                    aot = self._encode_aot.get(k)
                    if aot is not None:
                        logits = aot(self.params, tokens, *self._nargs())
                    elif sc.one_shape:
                        logits = self._encode_one[k](self.params, tokens,
                                                     *self._nargs())
                    else:
                        logits = self._encode(self.params, tokens,
                                              *self._nargs())
                    # encodes are billed at bucket k: the packed prefix is
                    # contiguous, so the accelerator's static schedule streams
                    # only the k live rows through every core. Padded rows
                    # ([n_real:]) are never predicted or accounted.
                    preds = jnp.argmax(logits[:fb.n_real], -1)
                    if inj is not None:
                        stall = inj.stall_s(k, tag)
                        if stall > 0:
                            # injected straggler: the flush completes but slow
                            # — the watchdog's detection target
                            preds.block_until_ready()
                            time.sleep(stall)
                    break
                except TransientFault as e:
                    attempt += 1
                    for sid in {s for s, _ in fb.frame_idx}:
                        if sid in by_sid:
                            by_sid[sid].retries += 1
                    if attempt > sc.retry_limit:
                        raise SessionFailure(
                            sorted({s for s, _ in fb.frame_idx}),
                            f"retry limit ({sc.retry_limit}) exhausted: {e}",
                        ) from e
                    time.sleep(min(sc.retry_backoff_s * 2 ** (attempt - 1),
                                   1.0))
                except FatalFault as e:
                    raise SessionFailure(sorted({s for s, _ in fb.frame_idx}),
                                         str(e)) from e
            if timed:
                # observed flush latency: launch to materialized result. The
                # sync costs the autotuned path its async overlap — accepted,
                # it is what makes the telemetry the controller calibrates
                # against an honest per-flush number.
                preds.block_until_ready()
                spans.host_syncs += 1
                wall = sp.elapsed_s()
                if self.controller is not None:
                    self.controller.record_flush(k, fb.n_real, len(owners),
                                                 wall, rnd)
                elif self.telemetry is not None:
                    # watchdog-only path: feed the straggler detector directly
                    self.telemetry.record(k, fb.n_real, sc.microbatch,
                                          len(owners), wall, rnd)
            for sid, (rows, fidxs) in owners.items():
                sess = by_sid[sid]
                sess.record_flush(k, len(rows))
                if timed:
                    sess.acct.add_flush_wall(k, wall)
                sess.add_deferred(fidxs, preds if len(owners) == 1
                                  else preds[np.asarray(rows)])
            self.flush_log.append((sids, k, fb.n_real))
            spans.flushes += 1
            spans.rows_launched += fb.tokens.shape[0]
            spans.rows_real += fb.n_real
            # the device ages by the frames this flush pushed through it; the
            # flush itself observed the pre-advance state
            self._advance_drift(fb.n_real)

    # -- checkpoint / restore / migration ----------------------------------

    def _compat(self) -> dict:
        """The configuration surface a snapshot is only valid under: any
        mismatch between writer and reader changes routing, shapes, or
        numerics, so restore refuses rather than silently diverging."""
        sc = self.serve_cfg
        return {
            "img_size": self.cfg.img_size, "patch": self.cfg.patch,
            "ladder": [int(k) for k in self.ladder.sizes],
            "chunk": sc.chunk, "microbatch": sc.microbatch,
            "mask_refresh": sc.mask_refresh,
            "delta_threshold": sc.delta_threshold,
            "one_shape": bool(sc.one_shape),
            "fingerprint": str(self.policy.fingerprint()),
            "noise": repr(self.noise),
        }

    def _check_compat(self, compat: dict) -> None:
        mine = self._compat()
        diffs = [f"{k}: snapshot={compat.get(k)!r} server={mine[k]!r}"
                 for k in mine if compat.get(k) != mine[k]]
        if diffs:
            raise ValueError("snapshot is incompatible with this server "
                             "(restore would not be bitwise): "
                             + "; ".join(diffs))

    def _pending_of(self, sid: int, remove: bool = False) -> list:
        """This session's queued-but-unflushed batcher entries as plain
        descriptors (tokens device->host). Exporting (not pad-flushing)
        them is what preserves the per-launch absmax scopes of the flushes
        they will eventually join — the bitwise-resume requirement."""
        if self.batcher is None:
            return []
        sel = lambda key: isinstance(key, tuple) and key[1] == sid
        out = []
        for key, t, ix, now, is_row in self.batcher.export(sel):
            out.append({"bucket": int(key[0]), "now": int(now),
                        "is_row": bool(is_row),
                        "fidx": [int(f) for _, f in ix],
                        "tokens": np.asarray(jax.device_get(t))})
        if remove and out:
            self.batcher.discard(sel)
        return out

    def _snapshot(self, live, rnd: int, offset: int) -> tuple[dict, dict]:
        """Flatten server + per-session state into (arrays, extra) for
        ``repro.checkpoint.save``. Controller/autotune state is *not*
        captured: a restored server re-warms and re-calibrates its control
        plane (documented in README) — only prediction-bearing state must
        round-trip bitwise."""
        arrays: dict = {}
        metas = []
        for s in live:
            s_arrays, meta = s.state_dict()
            pend = self._pending_of(s.sid)
            for j, p in enumerate(pend):
                arrays[f"s{s.sid}/pend{j}"] = p.pop("tokens")
            meta["pending"] = pend
            for key, a in s_arrays.items():
                arrays[f"s{s.sid}/{key}"] = a
            metas.append(meta)
        if self.drift is not None:
            arrays["drift/key"] = np.asarray(self.drift.key)
            arrays["drift/frame"] = np.asarray(self.drift.frame)
            arrays["drift/nm"] = np.asarray(self.drift.drift_nm)
        extra = {"sessions": metas, "rnd": int(rnd), "offset": int(offset),
                 "recalibrations": int(self.recalibrations),
                 "host_drift_nm": float(self._host_drift_nm),
                 "next_sid": int(self._next_sid),
                 "compat": self._compat()}
        return arrays, extra

    def checkpoint(self, root: str | None = None,
                   step: int | None = None) -> str:
        """Snapshot every live session (frame cursor, mask cache,
        accounting, deferred predictions, queued rows) plus the server's
        DriftState and loop cursors to ``root/step_<n>`` (atomic
        tmp+rename via ``repro.checkpoint``). Valid mid-serve (between
        rounds — ``serve(max_rounds=...)`` or the ``checkpoint_every``
        cadence) or between serves. Returns the written path."""
        sc = self.serve_cfg
        root = root or sc.checkpoint_dir
        if not root:
            raise ValueError("checkpoint needs a root (checkpoint_dir "
                             "config or the root argument)")
        if sc.mix_streams:
            raise ValueError(
                "checkpoint is unsupported under mix_streams: queued rows "
                "are cross-session, so per-session state cannot be "
                "snapshotted without changing absmax scopes")
        if self._inflight is not None:
            st = self._inflight
            live, rnd, offset = st["live"], st["rnd"], st["offset"]
        else:
            live = [s for s in self._sessions if not s.finished]
            rnd, offset = 0, 0
        arrays, extra = self._snapshot(live, rnd, offset)
        step = int(rnd if step is None else step)
        if self._injector is not None:
            self._injector.checkpoint_io(step)   # may raise CheckpointFault
        os.makedirs(root, exist_ok=True)
        path = os.path.join(root, f"step_{step}")
        _ckpt_save(path, arrays, step=step, extra=extra)
        self._ckpt_gc(root)
        return path

    def _ckpt_gc(self, root: str) -> None:
        keep = self.serve_cfg.checkpoint_keep
        if keep <= 0:
            return
        steps = sorted((int(d.split("_", 1)[1]), d)
                       for d in os.listdir(root)
                       if d.startswith("step_")
                       and d.split("_", 1)[1].isdigit())
        for _, d in steps[:-keep]:
            shutil.rmtree(os.path.join(root, d), ignore_errors=True)

    def restore_checkpoint(self, path_or_root: str,
                           streams: dict | None = None) -> dict:
        """Rebuild sessions from a snapshot written by ``checkpoint()``
        into this (fresh) server; the next ``serve()`` resumes at the
        snapshot's round/rotation cursors and produces the remaining
        predictions bitwise identically to the uninterrupted run.

        Accepts either a concrete ``step_<n>`` directory or a root (the
        newest step is taken). ``streams`` maps sid -> VideoStream for
        frame sources that did not serialize (a snapshot of a plain
        ``VideoStream`` dataclass restores without it). Returns the
        restored ``{sid: StreamSession}``."""
        if self._inflight is not None:
            raise ValueError("cannot restore into a mid-serve server")
        if any(not s.finished for s in self._sessions):
            raise ValueError("cannot restore into a server with live "
                             "sessions (would collide with their sids)")
        path = path_or_root
        if not os.path.exists(os.path.join(path, "meta.json")):
            step = latest_step(path_or_root)
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoint under {path_or_root}")
            path = os.path.join(path_or_root, f"step_{step}")
        arrays, _, extra = restore_flat(path)
        self._check_compat(extra.get("compat", {}))
        streams = streams or {}
        sessions: dict[int, StreamSession] = {}
        for meta in extra["sessions"]:
            sid = int(meta["sid"])
            pre = f"s{sid}/"
            sub = {k[len(pre):]: v for k, v in arrays.items()
                   if k.startswith(pre)}
            s = StreamSession.from_state(
                sub, meta, self.serve_cfg, self.cfg, ladder=self.ladder,
                layer_bits=self.layer_bits,
                stream=streams.get(sid, streams.get(str(sid))))
            sessions[sid] = s
            self._sessions.append(s)
        self._next_sid = max(int(extra.get("next_sid", 0)),
                             max(sessions, default=-1) + 1)
        if self.noise is not None and "drift/key" in arrays:
            self.drift = DriftState(jnp.asarray(arrays["drift/key"]),
                                    jnp.asarray(arrays["drift/frame"]),
                                    jnp.asarray(arrays["drift/nm"]))
            self._host_drift_nm = float(extra.get("host_drift_nm", 0.0))
        self.recalibrations = int(extra.get("recalibrations", 0))
        self._resume = (int(extra["rnd"]), int(extra["offset"]))
        return sessions

    def _restore_pending(self, live) -> list:
        """Re-queue restored sessions' exported batcher rows (same groups,
        same ``now`` ticks — see ``MicroBatcher.export``). Any flush that
        becomes ready immediately is returned for execution before the
        first resumed round (cannot happen for a snapshot that respected
        the < microbatch queue invariant, but is handled anyway)."""
        early = []
        for s in live:
            pend = getattr(s, "_pending_restore", None)
            if not pend:
                continue
            for bucket, toks, fidx, now, is_row in pend:
                key = (bucket, s.sid)
                pairs = [(s.sid, int(f)) for f in fidx]
                toks = jnp.asarray(toks)
                if is_row:
                    early.extend(self.batcher.push(key, toks, pairs[0],
                                                   now=now))
                else:
                    early.extend(self.batcher.push_many(key, toks, pairs,
                                                        now=now))
            s._pending_restore = None
        return early

    # -- session migration -------------------------------------------------

    def export_session(self, sid: int) -> dict:
        """Extract one live session — its full state plus its queued
        batcher rows — as a host-side snapshot dict for ``adopt_session``
        on another server. The session leaves this server (its queues are
        discarded after export; it is marked finished). Legal mid-serve
        only while paused (``serve(max_rounds=...)`` returned ``{}``)."""
        if self.serve_cfg.mix_streams:
            raise ValueError("migration is unsupported under mix_streams")
        s = next((s for s in self._sessions
                  if s.sid == sid and not s.finished), None)
        if s is None:
            raise KeyError(f"no live session {sid}")
        arrays, meta = s.state_dict()
        meta["pending"] = self._pending_of(sid, remove=True)
        if self._inflight is not None:
            self._inflight["live"] = [x for x in self._inflight["live"]
                                      if x.sid != sid]
        self._sessions = [x for x in self._sessions if x.sid != sid]
        s.finished = True
        return {"arrays": arrays, "meta": meta, "compat": self._compat()}

    def adopt_session(self, snapshot: dict, stream=None) -> StreamSession:
        """Adopt a session exported by another server mid-stream. The
        remaining predictions are bitwise identical to staying put:
        micro-batches are session-pure, so numerics depend only on the
        session's own frames and the (identical, compat-checked) weights
        — not on which server launches them. Exception: under ``noise``,
        the DriftState is server-owned shared thermal history, so a
        migrated session sees the *destination's* drift trajectory (real
        hardware would too — documented, not hidden)."""
        if self._inflight is not None:
            raise ValueError("cannot adopt mid-serve (pause first)")
        if self.serve_cfg.mix_streams:
            raise ValueError("migration is unsupported under mix_streams")
        self._check_compat(snapshot["compat"])
        meta = snapshot["meta"]
        sid = int(meta["sid"])
        if any(s.sid == sid and not s.finished for s in self._sessions):
            raise ValueError(f"sid {sid} already live on this server")
        s = StreamSession.from_state(snapshot["arrays"], meta,
                                     self.serve_cfg, self.cfg,
                                     ladder=self.ladder,
                                     layer_bits=self.layer_bits,
                                     stream=stream)
        self._sessions.append(s)
        self._next_sid = max(self._next_sid, sid + 1)
        return s

    # -- single-stream dense baseline --------------------------------------

    def run_dense(self, stream: VideoStream, n_frames: int = 64,
                  start: int = 0) -> StreamResult:
        """Mask-mode dense baseline: identical gating, but every frame is
        encoded at all N patches with the RoI mask applied on the attention
        key axis — compute is *not* reduced. The bucketed path's frames/s
        win over this is the serving subsystem's raison d'etre."""
        s = StreamSession(-1, stream, n_frames, start, self.serve_cfg,
                          self.cfg, ladder=None, layer_bits=self.layer_bits)
        t0 = time.time()
        while True:
            batch = s.next_batch()
            if batch is None:
                break
            frames, idxs = batch["frames"], batch["frame_idx"]
            valid = idxs < s.limit
            scores_np, n_scored = s.cache.gate(batch["frames_host"], idxs,
                                               self._score_fn,
                                               eligible=valid)
            s.acct.add_mgnet(n_scored)
            mask = (jax.nn.sigmoid(jnp.asarray(scores_np))
                    > self.mcfg.t_reg).astype(jnp.float32)
            logits = self._encode_dense(self.params, frames, mask,
                                        *self._nargs())
            s.acct.add_encode(self.n_patches, int(valid.sum()))
            s.add_deferred([int(i) for i in idxs],
                           jnp.argmax(logits, -1))
            self._advance_drift(int(valid.sum()), extra_sessions=(s,))
        res = s.finish(time.time() - t0)
        res.bucket_hits = {self.n_patches: res.frames}
        return res


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def main(argv=None):
    from repro.serving.engine import _smoke_cfg

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU config (32x32 frames, 4 layers)")
    ap.add_argument("--variant", default="tiny")
    ap.add_argument("--img-size", type=int, default=96)
    ap.add_argument("--backend", default="photonic_pallas",
                    help=f"matmul backend ({', '.join(available_backends())})")
    ap.add_argument("--attn-backend", default="", choices=["", "xla", "flash"])
    ap.add_argument("--ffn-backend", default="", choices=["", "xla", "fused"])
    ap.add_argument("--streams", type=int, default=4,
                    help="number of concurrent camera sessions")
    ap.add_argument("--frames", type=int, default=64,
                    help="frames per stream")
    ap.add_argument("--phase", type=int, default=16,
                    help="per-stream start offset (stream i starts at i*phase)")
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=4)
    ap.add_argument("--mask-refresh", type=int, default=8)
    ap.add_argument("--delta-threshold", type=float, default=0.15)
    ap.add_argument("--buckets", default="0.25,0.5,0.75,1.0")
    ap.add_argument("--one-shape", action="store_true")
    ap.add_argument("--cut-every", type=int, default=32)
    ap.add_argument("--max-wait", type=int, default=0,
                    help="pad-flush partial micro-batches after this many "
                         "scheduling rounds (0: wait for fill or stream end)")
    ap.add_argument("--mix-streams", action="store_true",
                    help="fill micro-batches across sessions (max "
                         "saturation; couples w8a8 activation scales "
                         "across streams)")
    ap.add_argument("--trim-dead-buckets", action="store_true",
                    help="route-only calibration pass, then drop ladder "
                         "buckets no stream hits before warming the jit set")
    ap.add_argument("--calib-frames", type=int, default=0,
                    help="frames per stream for --trim-dead-buckets "
                         "calibration (default 2 chunks)")
    ap.add_argument("--bit-plan", default="",
                    help="mixed-precision bit plan: comma per-layer widths "
                         "('8,6,4,8'), a JSON literal, or a JSON file path "
                         "(core/bitalloc.py formats)")
    ap.add_argument("--bit-budget", type=float, default=0.0,
                    help="> 0: calibrate a per-layer plan to this target "
                         "mean bit width at startup (sensitivity-driven, "
                         "overrides --bit-plan)")
    ap.add_argument("--no-warm-start", action="store_true",
                    help="skip the eager jit-ladder warm-up (first flushes "
                         "then pay their compiles)")
    ap.add_argument("--autotune", action="store_true",
                    help="serving control plane: route-probe the ladder, "
                         "price hit buckets with the HLO cost model (the "
                         "compiles double as AOT encode executables), then "
                         "re-tune the scheduling knobs online with "
                         "hysteresis + safety clamp")
    ap.add_argument("--retune-every", type=int, default=32,
                    help="frames between controller evaluations")
    ap.add_argument("--assert-converged", action="store_true",
                    help="exit nonzero unless the controller calibrated "
                         "and settled (the CI smoke gate)")
    ap.add_argument("--mesh", default="auto", choices=["auto", "off"],
                    help="shard the encode batch axis over visible devices")
    ap.add_argument("--model-shards", type=int, default=0,
                    help="> 1: 2-D (data, model) serving mesh — attention "
                         "heads + d_ff shard over the model axis and the "
                         "fused encode runs under shard_map, bitwise-equal "
                         "to unsharded (needs n_heads and d_ff divisible)")
    ap.add_argument("--noise", action="store_true",
                    help="run with calibrated device noise (FPV + shot + "
                         "MR drift, core/noise.py NoiseSpec); off = clean, "
                         "bitwise-identical dispatch")
    ap.add_argument("--fpv-sigma", type=float, default=0.01,
                    help="fabrication process variation sigma (static "
                         "per-trace multiplicative weight noise)")
    ap.add_argument("--shot-sigma", type=float, default=0.005,
                    help="per-readout shot/thermal noise sigma")
    ap.add_argument("--q-factor", type=float, default=5000.0,
                    help="MR quality factor of the noise operating point")
    ap.add_argument("--drift-rate-nm", type=float, default=0.0,
                    help="resonance drift accumulated per served frame (nm)")
    ap.add_argument("--wander-sigma-nm", type=float, default=0.0,
                    help="per-element resonance wander sigma around the "
                         "common-mode drift (nm)")
    ap.add_argument("--recal-bound-nm", type=float, default=0.0,
                    help="> 0: trigger online recalibration (requantize + "
                         "drift reset, billed as an MR re-tune) when "
                         "accumulated drift crosses this bound")
    ap.add_argument("--adc-quant", action="store_true",
                    help="quantize noisy readouts through the 8-bit ADC "
                         "transfer function")
    ap.add_argument("--noise-seed", type=int, default=0,
                    help="seed of the device-noise RNG lineage")
    ap.add_argument("--flush-fault-rate", type=float, default=0.0,
                    help="probability a flush site raises a (retryable) "
                         "transient device fault")
    ap.add_argument("--flush-fatal-rate", type=float, default=0.0,
                    help="probability a flush site raises a fatal fault "
                         "(quarantines the owning session)")
    ap.add_argument("--ingest-fault-rate", type=float, default=0.0,
                    help="probability an ingest chunk raises a transient "
                         "fault (chunk retried next round)")
    ap.add_argument("--stall-rate", type=float, default=0.0,
                    help="probability a flush stalls (injected straggler)")
    ap.add_argument("--stall-s", type=float, default=0.05,
                    help="injected stall duration (seconds)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the fault-injection RNG lineage")
    ap.add_argument("--hard-fail-session", type=int, default=-1,
                    help=">= 0: hard-fail this session id at its first "
                         "ingest (isolation demo)")
    ap.add_argument("--retry-limit", type=int, default=3,
                    help="transient-fault retries per flush before the "
                         "owning session is quarantined")
    ap.add_argument("--watchdog", action="store_true",
                    help="flush watchdog: median+MAD straggler detection "
                         "over per-flush wall times")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="> 0: bound on queued micro-batch rows; ingest "
                         "chunks arriving over the bound are shed")
    ap.add_argument("--checkpoint-dir", default="",
                    help="root directory for session checkpoints")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="> 0: snapshot every N scheduling rounds to "
                         "--checkpoint-dir")
    ap.add_argument("--json", default="",
                    help="write per-session + aggregate results to this path")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.backend and args.backend not in available_backends():
        raise SystemExit(f"unknown backend {args.backend!r}; "
                         f"choose from {available_backends()}")
    if args.smoke:
        cfg = _smoke_cfg(args.backend, args.attn_backend, args.ffn_backend)
    else:
        from repro.configs.opto_vit import get_config
        cfg = get_config(args.variant, img_size=args.img_size,
                         mgnet=True).with_(matmul_backend=args.backend,
                                           attn_backend=args.attn_backend,
                                           ffn_backend=args.ffn_backend)
    if args.noise:
        cfg = cfg.with_(noise=NoiseSpec(
            q_factor=args.q_factor, fpv_sigma=args.fpv_sigma,
            shot_sigma=args.shot_sigma, drift_rate_nm=args.drift_rate_nm,
            wander_sigma_nm=args.wander_sigma_nm,
            recal_bound_nm=args.recal_bound_nm,
            adc_quantize_output=args.adc_quant, seed=args.noise_seed))

    bit_plan = ()
    if args.bit_plan:
        from repro.core.bitalloc import parse_bit_plan
        bit_plan = parse_bit_plan(args.bit_plan) or ()
    faults = None
    if (args.flush_fault_rate > 0 or args.flush_fatal_rate > 0
            or args.ingest_fault_rate > 0 or args.stall_rate > 0
            or args.hard_fail_session >= 0):
        faults = FaultSpec(flush_fault_rate=args.flush_fault_rate,
                           flush_fatal_rate=args.flush_fatal_rate,
                           ingest_fault_rate=args.ingest_fault_rate,
                           stall_rate=args.stall_rate, stall_s=args.stall_s,
                           hard_fail_session=args.hard_fail_session,
                           seed=args.fault_seed)
    server_cfg = ServerConfig(
        bucket_fractions=tuple(float(f) for f in args.buckets.split(",")),
        microbatch=args.microbatch, chunk=args.chunk,
        mask_refresh=args.mask_refresh,
        delta_threshold=args.delta_threshold, one_shape=args.one_shape,
        max_wait_chunks=args.max_wait, mix_streams=args.mix_streams,
        warm_start=False, mesh=args.mesh, model_shards=args.model_shards,
        bit_plan=bit_plan,
        autotune=args.autotune, retune_every=args.retune_every,
        faults=faults, retry_limit=args.retry_limit,
        watchdog=args.watchdog, max_pending_rows=args.max_pending,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every)
    server = StreamServer(cfg, server_cfg)
    print(f"[server] {cfg.name} {cfg.img_size}x{cfg.img_size} "
          f"backend={server.policy.resolve_backend()} "
          f"attn={server.policy.resolve_attn_backend()} "
          f"ffn={server.policy.resolve_ffn_backend()} "
          f"bits={list(server.layer_bits) if server.layer_bits else (cfg.quant_bits or 8)} "
          f"ladder={list(server.ladder.sizes)} of {server.n_patches} patches "
          f"mesh={'x'.join(str(n) for n in server.mesh.devices.shape) if server.mesh else 'off'}"
          + (f" noise=Q{server.noise.q_factor:g}"
             f"/fpv{server.noise.fpv_sigma:g}/shot{server.noise.shot_sigma:g}"
             if server.noise is not None else ""))

    streams = video_fleet(args.streams, img_size=cfg.img_size,
                          patch=cfg.patch, cut_every=args.cut_every)
    sessions = [server.add_session(st, n_frames=args.frames,
                                   start=i * args.phase)
                for i, st in enumerate(streams)]

    if args.trim_dead_buckets:
        removed = server.calibrate_trim(args.calib_frames or None)
        print(f"[server] calibration trimmed buckets {list(removed)} -> "
              f"ladder {list(server.ladder.sizes)}")
    if args.bit_budget > 0:
        plan = server.calibrate_bits(args.bit_budget,
                                     args.calib_frames or None)
        print(f"[server] bit calibration -> per-layer plan {list(plan)} "
              f"(mean {sum(plan) / len(plan):.2f} bits, "
              f"target {args.bit_budget:g})")
    if args.autotune:
        server.autotune_prepare(args.calib_frames or None)
        print(f"[server] autotune: priced buckets "
              f"{sorted(server.cost_model.costs)} "
              f"(ladder {list(server.ladder.sizes)}), "
              f"{len(server._encode_aot)} AOT executables, "
              f"non-encode jits warmed in {server.warm_s:.2f}s")
        print(server.cost_model.render())
    elif not args.no_warm_start:
        server.warm_start()
        print(f"[server] jit ladder warmed in {server.warm_s:.2f}s "
              f"({len(server.ladder.sizes)} buckets)")

    results = server.serve(verbose=True)
    total = sum(r.frames for r in results.values())
    wall = max((r.wall_s for r in results.values()), default=0.0)
    for s in sessions:
        r = results[s.sid]
        tag = f" POISONED ({r.failure})" if r.poisoned else ""
        print(f"[server] session {s.sid}:", r.summary() + tag)
    agg_fps = total / wall if wall > 0 else 0.0
    print(f"[server] aggregate: {total} frames over {len(sessions)} streams "
          f"in {wall:.2f}s -> {agg_fps:.1f} frames/s "
          f"(warm-up {server.warm_s:.2f}s, "
          f"{len(server.flush_log)} encode launches)")
    if server.noise is not None:
        print(f"[server] noise: drift {server._host_drift_nm:.3f} nm "
              f"residual, {server.recalibrations} recalibrations")
    if server._injector is not None:
        print(f"[server] faults: {server._injector.report()}")
    if server._watchdog:
        print(f"[server] watchdog: {len(server.straggler_flags)} "
              f"straggler flushes flagged")
    if server.controller is not None:
        print("[server]", server.controller.report())
        assert server.controller.clamp_violations == 0, (
            "controller applied knobs outside the safety clamp: "
            f"{server.controller.clamp_violations} violations")
        if args.assert_converged:
            assert server.controller.converged, (
                "controller did not converge: "
                + server.controller.report())

    if args.json:
        payload = {
            "streams": len(sessions), "frames_total": total,
            "aggregate_fps": agg_fps, "warm_s": server.warm_s,
            "ladder": list(server.ladder.sizes),
            "layer_bits": (list(server.layer_bits)
                           if server.layer_bits else None),
            "noise": (None if server.noise is None else {
                "q_factor": server.noise.q_factor,
                "fpv_sigma": server.noise.fpv_sigma,
                "shot_sigma": server.noise.shot_sigma,
                "drift_rate_nm": server.noise.drift_rate_nm,
                "recal_bound_nm": server.noise.recal_bound_nm,
                "recalibrations": server.recalibrations,
            }),
            "faults": (None if server._injector is None
                       else dict(server._injector.injected)),
            "sessions": {
                str(s.sid): {
                    "frames": results[s.sid].frames,
                    "fps": results[s.sid].fps,
                    "kfps_per_watt": results[s.sid].kfps_per_watt,
                    "mean_bits": results[s.sid].mean_bits,
                    "recalibrations": results[s.sid].recalibrations,
                    "bucket_hits": results[s.sid].bucket_hits,
                    "predictions": results[s.sid].predictions,
                    "poisoned": results[s.sid].poisoned,
                    "failure": results[s.sid].failure,
                    "retries": results[s.sid].retries,
                    "shed_frames": results[s.sid].shed_frames,
                } for s in sessions},
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"[server] wrote {args.json}")
    return results


if __name__ == "__main__":
    main()
