"""Unified matmul execution backend: registry + quantize-once weight cache.

The paper's optical core tunes each MR weight tile *once* and then streams
activations through it (Fig. 6); re-deriving weight quantization scales on
every forward call has no hardware analogue and wastes the dominant dataflow
lever (Lightening-Transformer makes the same observation for DPTC arrays).
This module is the software analogue of that design point:

  * ``ExecPolicy``        - execution-mode knobs threaded from ArchConfig
    into every layer (moved here from models/layers.py so core modules can
    route through the same dispatch without a models dependency),
  * a **backend registry** of interchangeable matmul implementations::

        bf16            plain MXU dot (f32 accumulate), the LM default
        qat             fake-quant w8a8 (STE in training) - paper SIV
        photonic_sim    chunk-walking w8a8 integer oracle (Fig. 6 schedule)
        photonic_pallas int8 Pallas MXU kernel (kernels/photonic_matmul.py)

    All photonic backends share one numerics contract: their int32
    accumulates are bit-identical to ``photonic_matmul_exact`` (enforced by
    tests/test_backend_parity.py),
  * ``QuantizedWeight`` + ``prepare_params``: the **quantize-once cache**.
    ``prepare_params`` walks a param pytree and replaces every matmul weight
    with its pre-computed int8 codes + per-output-channel scale (the MR
    tuning step). The per-call photonic path then does only activation
    quantization + integer matmul + dequant.

``linear`` is the single entry point every model matmul funnels through.

Attention has its own (smaller) registry: the score-softmax-PV core of
every MHSA dataflow funnels through ``attend``, dispatching between

    xla     materialized (Sq, Skv) scores + additive key-mask bias +
            jax.nn.softmax — the reference dataflow
    flash   fused RoI-masked streaming-softmax flash attention
            (kernels/flash_attention.py): pruned KV blocks are skipped, so
            masked patches cost zero score FLOPs on the serving hot path.
            Lowers to the Pallas kernel on TPU and to the XLA twin with
            static packed-skip on CPU hosts (``ExecPolicy.interpret``)

selected by ``ExecPolicy.attn_backend`` (ArchConfig.attn_backend). The two
backends agree to streaming-softmax reassociation noise (enforced per
dataflow by tests/test_differential.py).

The GELU-MLP has a third registry (``FFN_BACKENDS``) behind the same
policy object — ``ExecPolicy.ffn_backend`` / ``ArchConfig.ffn_backend``:

    xla     composed two-``linear`` dispatch with the float GELU
            round-trip between them — the reference dataflow, runs on
            every matmul backend
    fused   the fused int8 photonic FFN (kernels/fused_ffn.py): w1-matmul
            + bias + GELU + requant + w2-matmul in one kernel, the
            (B, S, d_ff) hidden state never reaching HBM; packed
            ``live_rows`` skips fully-pruned token rows. Requires the
            int8 Pallas matmul backend + quantize-once cached w1/w2 at
            <= 8-bit (possibly different — mixed-precision bit plans)
            widths — anything else falls back to the composed dispatch
            with a one-time warning (same auto-fallback contract as the
            fused MHSA hot path). Bit-identical to ``xla`` where both run.

``ffn`` is the dispatch point ``models/ffn.py::mlp`` funnels through.
"""

from __future__ import annotations

import math
import warnings
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import quant

__all__ = [
    "ExecPolicy",
    "interpret_default",
    "QuantizedWeight",
    "quantize_weight",
    "prepare_params",
    "warn_fused_fallback",
    "reset_fused_fallback_warnings",
    "register_backend",
    "get_backend",
    "available_backends",
    "register_attention_backend",
    "get_attention_backend",
    "available_attention_backends",
    "register_ffn_backend",
    "get_ffn_backend",
    "available_ffn_backends",
    "matmul",
    "linear",
    "attend",
    "ffn",
    "int_accumulate_exact",
    "int_accumulate_sim",
    "int_accumulate_pallas",
]

# photonic K-chunk width (32 WDM wavelength channels, paper Fig. 3b)
_WAVELENGTHS = 32


class ExecPolicy:
    """Execution-mode knobs threaded from ArchConfig into every layer.

    ``backend`` names a registry entry explicitly; when empty the legacy
    flags resolve it: photonic -> photonic_sim, quant_bits -> qat, else bf16.
    ``attn_backend`` names an attention-core registry entry ("" -> xla);
    ``ffn_backend`` an FFN registry entry ("" -> xla).
    ``interpret`` runs Pallas kernels in interpreter mode. Left unset it
    follows the platform the program runs on (``interpret_default``,
    asked when first read, so building a policy never starts a backend):
    the interpreter on CPU hosts, compiled kernels everywhere else. Tests
    that must steer it pass or assign it explicitly.
    ``bit_plan`` is the hashable identity of the active mixed-precision
    plan (``core.bitalloc.plan_key`` output, or a bare per-layer tuple) —
    None means uniform ``quant_bits``. Setting it does two things: the
    plan joins ``fingerprint()`` (so jit caches key on it) and
    ``_weight_bits`` accepts cached widths that differ from
    ``quant_bits`` (deliberate per-layer divergence instead of a stale
    cache, which without a plan is an error).
    """

    __slots__ = ("quant_bits", "photonic", "training", "dot_out_native",
                 "backend", "_interpret", "attn_backend", "ffn_backend",
                 "bit_plan", "noise")

    def __init__(self, quant_bits: int = 0, photonic: bool = False,
                 training: bool = True, dot_out_native: bool = False,
                 backend: str = "", interpret: bool | None = None,
                 attn_backend: str = "", ffn_backend: str = "",
                 bit_plan=None, noise=None):
        self.quant_bits = quant_bits
        self.photonic = photonic
        self.training = training
        self.dot_out_native = dot_out_native
        self.backend = backend
        self._interpret = interpret
        self.attn_backend = attn_backend
        self.ffn_backend = ffn_backend
        self.bit_plan = (tuple(bit_plan) if isinstance(bit_plan, list)
                         else bit_plan) or None
        # calibrated device-noise operating point (core/noise.py NoiseSpec,
        # hashable) — None is the clean path, bitwise identical to a policy
        # built before the field existed
        self.noise = noise

    @property
    def interpret(self) -> bool:
        if self._interpret is None:
            self._interpret = interpret_default()
        return self._interpret

    @interpret.setter
    def interpret(self, value: bool) -> None:
        self._interpret = value

    @staticmethod
    def from_cfg(cfg, training: bool = True) -> "ExecPolicy":
        return ExecPolicy(getattr(cfg, "quant_bits", 0),
                          getattr(cfg, "photonic", False), training,
                          getattr(cfg, "dot_out_native", False),
                          getattr(cfg, "matmul_backend", "") or "",
                          None,
                          getattr(cfg, "attn_backend", "") or "",
                          getattr(cfg, "ffn_backend", "") or "",
                          getattr(cfg, "bit_plan", None) or None,
                          getattr(cfg, "noise", None))

    def resolve_backend(self) -> str:
        if self.backend:
            return self.backend
        if self.photonic:
            return "photonic_sim"
        if self.quant_bits:
            return "qat"
        return "bf16"

    def resolve_attn_backend(self) -> str:
        return self.attn_backend or "xla"

    def resolve_ffn_backend(self) -> str:
        return self.ffn_backend or "xla"

    def is_photonic(self) -> bool:
        return self.resolve_backend().startswith("photonic")

    def without_noise(self) -> "ExecPolicy":
        """Clean copy of this policy (noise stripped); self when already
        clean, so clean policies keep object identity."""
        if self.noise is None:
            return self
        return ExecPolicy(self.quant_bits, self.photonic, self.training,
                          self.dot_out_native, self.backend, self.interpret,
                          self.attn_backend, self.ffn_backend, self.bit_plan,
                          None)

    def gate_policy(self) -> "ExecPolicy":
        """Policy for the MGNet RoI gate: by default the gate runs *clean*
        even under noise (its tiny electronic-side matmuls would otherwise
        make the routing — and hence every bucket shape — stochastic);
        ``NoiseSpec.noisy_gate`` opts the gate into the noise model."""
        if self.noise is None or self.noise.noisy_gate:
            return self
        return self.without_noise()

    def fingerprint(self) -> tuple:
        """Hashable identity of every dispatch-relevant knob — the jit
        cache key for policy-closing compiled entry points (models/vit.py
        keys its single-jit fused encoder on this)."""
        return (self.resolve_backend(), self.resolve_attn_backend(),
                self.resolve_ffn_backend(), self.quant_bits,
                bool(self.interpret), bool(self.training),
                bool(self.dot_out_native), self.bit_plan, self.noise)

    def __repr__(self):
        noise = ", noise=on" if self.noise is not None else ""
        return (f"ExecPolicy(backend={self.resolve_backend()!r}, "
                f"attn={self.resolve_attn_backend()!r}, "
                f"ffn={self.resolve_ffn_backend()!r}, "
                f"bits={self.quant_bits}, training={self.training}{noise})")


def interpret_default() -> bool:
    """Whether Pallas kernels must run in the interpreter: only where JAX's
    default backend is the CPU. The one place the execution mode is
    decided — on a TPU every kernel compiles, never silently emulated."""
    return jax.default_backend() == "cpu"


# --------------------------------------------------------------------------
# quantize-once weight cache
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class QuantizedWeight:
    """A matmul weight after MR tuning: int8 codes + per-out-channel scale.

    ``wq``: (..., K, N) int8 codes; ``scale``: (..., 1, N) f32. Leading dims
    carry scan-stacked layers — ``jax.lax.scan`` slices both leaves in step,
    so an in-scan slice is exactly the (K, N)/(1, N) pair the 2-D backends
    consume. Registered as a pytree so prepared params flow through jit/scan
    unchanged.

    ``bits`` is an int, or — for scan-stacked (L, K, N) weights under a
    mixed-precision bit plan — a length-L tuple of per-layer widths. The
    tuple lives in the pytree aux data, so a plan change retraces every
    jit that closes over the params (the treedef is the cache key). 2-D
    weights always carry an int; the scanned encoder slices stacked
    weights into equal-bits runs before any 2-D dispatch sees them
    (models/vit.py), so ``linear`` never meets a tuple.
    """

    def __init__(self, wq: jax.Array, scale: jax.Array, bits=8):
        self.wq = wq
        self.scale = scale
        self.bits = tuple(bits) if isinstance(bits, list) else bits

    def layer_bits(self, i: int) -> int:
        """Width of stacked layer ``i`` (an int ``bits`` is uniform)."""
        return self.bits[i] if isinstance(self.bits, tuple) else self.bits

    def uniform_bits(self) -> int | None:
        """The single width when uniform, else None (mixed stacked)."""
        if isinstance(self.bits, tuple):
            u = set(self.bits)
            return u.pop() if len(u) == 1 else None
        return self.bits

    @property
    def shape(self):
        return self.wq.shape

    @property
    def ndim(self):
        return self.wq.ndim

    def dequantize(self) -> jax.Array:
        return self.wq.astype(jnp.float32) * self.scale

    def tree_flatten(self):
        return (self.wq, self.scale), (self.bits,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, bits=aux[0])

    def __repr__(self):
        return f"QuantizedWeight(shape={self.wq.shape}, bits={self.bits})"


def quantize_weight(w: jax.Array, bits=8) -> QuantizedWeight:
    """Pre-compute int8 codes + scale for one weight (the MR tuning step).

    The scale reduces only the contraction axis (-2), i.e. per output
    channel *per layer* for scan-stacked (L, K, N) weights — numerically
    identical to the per-call ``absmax_scale(w2d, axis=0)`` of the dynamic
    photonic path, which is what makes cached and uncached execution
    bit-identical.

    ``bits`` may be a per-layer sequence for a scan-stacked weight (one
    entry per leading-dim layer): each layer slice is quantized at its own
    width — bit-identical to quantizing the 2-D slices separately — and
    the codes/scales re-stacked into one cache entry.
    """
    w32 = w.astype(jnp.float32)
    if isinstance(bits, (tuple, list)):
        bt = tuple(int(b) for b in bits)
        if w32.ndim < 3 or w32.shape[0] != len(bt):
            raise ValueError(
                f"per-layer bits {bt} need a scan-stacked "
                f"(L={len(bt)}, K, N) weight, got shape {w.shape}")
        if len(set(bt)) == 1:
            bits = bt[0]                       # uniform plan: int fast path
        else:
            parts = [quantize_weight(w32[i], bt[i]) for i in range(len(bt))]
            return QuantizedWeight(jnp.stack([p.wq for p in parts]),
                                   jnp.stack([p.scale for p in parts]), bt)
    scale = quant.absmax_scale(w32, bits=bits, axis=-2)     # (..., 1, N)
    return QuantizedWeight(quant.quantize(w32, scale, bits=bits), scale, bits)


# param-tree keys whose leaves must stay raw arrays even when they look like
# matmul weights: class tokens / position tables (added, never contracted),
# embedding tables (gathered), depthwise-conv kernels (indexed), and the MoE
# expert subtree (einsum dispatch, not routed through ``linear``).
NON_MATMUL_KEYS = frozenset({
    "cls", "pos", "cls_token", "pos_embed", "embed", "embedding", "tok_embed",
    "wte", "conv_w", "moe",
    "w_a", "w_x",   # RG-LRU recurrence gates: consumed raw in the f32 scan
})

# leaf keys that name a ``linear`` weight without the conventional "w"
# prefix (w / w1 / wq / wqkv / w_gate / ... are matched by prefix).
MATMUL_WEIGHT_EXTRA = frozenset({
    "head", "head_w", "in_proj", "out_proj", "gate_proj",
})


def _is_matmul_weight_key(name: str) -> bool:
    return name.startswith("w") or name in MATMUL_WEIGHT_EXTRA


def _path_key(entry) -> str:
    # DictKey(key=...) / GetAttrKey(name=...) / SequenceKey(idx=...)
    return str(getattr(entry, "key", getattr(entry, "name", "")))


def prepare_params(params, bits: int = 8, min_size: int = 128,
                   exclude: frozenset = NON_MATMUL_KEYS,
                   bit_plan=None, n_layers: int | None = None):
    """Quantize every matmul weight of a param pytree once (MR tuning pass).

    A leaf is tuned iff its key names a ``linear`` weight (``w*`` prefix or
    ``MATMUL_WEIGHT_EXTRA``), no path component is in ``exclude``, and it is
    a float tensor of ndim >= 2 with at least ``min_size`` elements. Biases,
    norm scales, cls/pos tables and embeddings stay full precision —
    mirroring the paper's choice of quantizing only the optical-core
    operands. Key-based selection (rather than shape-based) is what keeps
    scan-stacked 1-D leaves like a (L, d) ``ln_g`` out of the cache.
    Idempotent: already-quantized leaves pass through.

    ``bit_plan`` assigns non-uniform widths (core/bitalloc.py): a
    per-layer sequence (one width per encoder block, applied to every
    matmul weight of the scan-stacked ``blocks`` subtree) or a dict with
    per-tensor path-suffix overrides (``{"attn/wq": 4, "ffn/w2": (8, 6,
    6, 8)}``) plus optional ``"layers"`` / ``"default"`` keys. Weights
    outside ``blocks`` (patch embed, head, MGNet) take the plan's default
    (= ``bits`` unless overridden). ``n_layers`` sizes per-layer
    sequences; it defaults to the leading dim of the stacked ``blocks``
    leaves.
    """
    plan = None
    if bit_plan is not None:
        from repro.core import bitalloc     # lazy: bitalloc imports us
        if n_layers is None:
            n_layers = _infer_n_layers(params)
        plan = bitalloc.normalize_bit_plan(bit_plan, n_layers,
                                           default=bits)

    def _leaf_bits(path):
        if plan is None:
            return bits
        from repro.core import bitalloc
        names = tuple(_path_key(e) for e in path)
        return bitalloc.resolve_bits(plan, names)

    def _prep(path, leaf):
        if isinstance(leaf, QuantizedWeight):
            return leaf
        if not _is_matmul_weight_key(_path_key(path[-1])):
            return leaf
        if any(_path_key(e) in exclude for e in path):
            return leaf
        if leaf.ndim < 2 or leaf.size < min_size:
            return leaf
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        lb = _leaf_bits(path)
        if (isinstance(lb, tuple) and
                (leaf.ndim < 3 or leaf.shape[0] != len(lb))):
            lb = bits        # per-layer plan, non-stacked weight: default
        return quantize_weight(leaf, bits=lb)

    return jax.tree_util.tree_map_with_path(
        _prep, params, is_leaf=lambda x: isinstance(x, QuantizedWeight))


def place_params(params, logical_axes, ctx):
    """Pin a prepared param pytree onto a serving mesh with NamedSharding.

    ``logical_axes`` is the model's per-leaf logical-axis tree (e.g.
    ``models.vit.vit_logical_axes``); ``ctx`` a ShardingCtx whose rules
    map those axes to mesh axes (MODEL_RULES shards head-/d_ff-major dims
    over "model", replicates everything else). QuantizedWeight leaves
    place both the int8 codes and the f32 scales — the scale's size-1
    contraction dim falls back to replicated via the standard
    divisibility rule, so per-out-channel scales land wherever their
    columns do. Leaves whose rank does not match their axes entry (or
    with every axis unmapped) replicate.

    Placement is a *bandwidth* optimization: the sharded encoder's
    shard_map would resolve mismatched layouts with an automatic reshard,
    so correctness never depends on this — but placing the quantize-once
    cache at prepare time moves the weight bytes exactly once. Only call
    it when the sharded path will actually engage: committed model-axis
    shardings on params fed to the *unsharded* jit would make GSPMD weave
    collectives into a graph whose bitwise contract assumes none.
    """
    from repro.distributed.sharding import named_sharding

    def _place(w, ax):
        axt = tuple(ax)
        if isinstance(w, QuantizedWeight):
            wq = jax.device_put(w.wq, named_sharding(w.wq.shape, axt, ctx))
            sc = jax.device_put(w.scale,
                                named_sharding(w.scale.shape, axt, ctx))
            return QuantizedWeight(wq, sc, w.bits)
        if getattr(w, "ndim", -1) == len(axt):
            return jax.device_put(w, named_sharding(w.shape, axt, ctx))
        return w

    return jax.tree_util.tree_map(
        _place, params, logical_axes,
        is_leaf=lambda x: isinstance(x, QuantizedWeight))


def _infer_n_layers(params) -> int:
    """Leading dim of the scan-stacked ``blocks`` leaves (plan sizing)."""
    blocks = params.get("blocks") if isinstance(params, dict) else None
    if blocks is not None:
        for leaf in jax.tree_util.tree_leaves(blocks):
            if getattr(leaf, "ndim", 0) >= 1:
                return int(leaf.shape[0])
    raise ValueError("cannot infer n_layers for a per-layer bit plan: "
                     "no stacked 'blocks' subtree — pass n_layers=")


def _resolve_wq(w, bits: int):
    """(int8 codes (K, N), scale (1, N) f32) from raw or cached weight."""
    if isinstance(w, QuantizedWeight):
        return w.wq, w.scale
    w32 = w.astype(jnp.float32)
    sw = quant.absmax_scale(w32, bits=bits, axis=-2)
    return quant.quantize(w32, sw, bits=bits), sw


def _weight_bits(w, p: ExecPolicy) -> int:
    """Effective width for a 2-D dispatch: the cached width when the weight
    is quantize-once cached, else ``policy.quant_bits``. A cached width
    that *disagrees* with an explicit ``quant_bits`` is an error unless a
    bit plan is active (``policy.bit_plan``) — silently preferring the
    cache hid stale-cache bugs (params prepared at one width, policy
    asking another)."""
    if isinstance(w, QuantizedWeight):
        if isinstance(w.bits, tuple):
            raise ValueError(
                f"stacked mixed-bits QuantizedWeight (bits={w.bits}) "
                f"reached a 2-D matmul dispatch; slice it to one layer "
                f"first (the segmented-scan encoder in models/vit.py does "
                f"this — see QuantizedWeight.layer_bits)")
        if p.quant_bits and p.bit_plan is None and w.bits != p.quant_bits:
            raise ValueError(
                f"cached QuantizedWeight.bits={w.bits} disagrees with "
                f"ExecPolicy.quant_bits={p.quant_bits} and no bit plan is "
                f"active — re-run prepare_params at the policy's width, "
                f"set quant_bits=0 to defer to the cache, or set "
                f"ExecPolicy.bit_plan for deliberate mixed precision")
        return w.bits
    return p.quant_bits or 8


def _out_dim(w) -> int:
    return w.shape[-1]


# --------------------------------------------------------------------------
# fused-path fallback warnings (the 12x cliff should never be invisible)
# --------------------------------------------------------------------------

# (component, fingerprint, reason) triples already warned about — one
# warning per distinct cause per policy, not one per forward call.
_FUSED_FALLBACK_WARNED: set = set()


def warn_fused_fallback(component: str, p: ExecPolicy, reason: str) -> None:
    """One-time ``UserWarning`` when a *requested* fused path (encoder /
    FFN / attention) silently takes composed dispatch instead. Keyed by
    (component, policy fingerprint, reason) so a steady-state serving loop
    warns exactly once per cause; silent when the fused path actually
    runs. Call sites only invoke this when the policy asked for the fused
    path (``ffn_backend="fused"`` / ``attn_backend="flash"``)."""
    key = (component, p.fingerprint(), reason)
    if key in _FUSED_FALLBACK_WARNED:
        return
    _FUSED_FALLBACK_WARNED.add(key)
    warnings.warn(
        f"fused {component} path fell back to composed dispatch: {reason} "
        f"(policy {p!r}) — expect ~an-order-of-magnitude slower serving; "
        f"see README 'Fused-path eligibility'", UserWarning, stacklevel=3)


def reset_fused_fallback_warnings() -> None:
    """Forget which fallbacks have been warned about (test isolation)."""
    _FUSED_FALLBACK_WARNED.clear()


# --------------------------------------------------------------------------
# integer-accumulate primitives (the cross-backend numerics contract)
# --------------------------------------------------------------------------

def int_accumulate_exact(xq: jax.Array, wq: jax.Array) -> jax.Array:
    """One-shot int32 accumulate — photonic_matmul_exact's inner product."""
    return jax.lax.dot_general(xq.astype(jnp.int32), wq.astype(jnp.int32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def int_accumulate_sim(xq: jax.Array, wq: jax.Array,
                       chunk: int = _WAVELENGTHS) -> jax.Array:
    """Chunk-walking int32 accumulate over K in ``chunk``-wide wavelength
    groups (Fig. 6 schedule). Integer addition is associative, so this is
    bit-identical to ``int_accumulate_exact`` — the oracle the Pallas
    kernel's K-grid walk must also match."""
    m, k = xq.shape
    n = wq.shape[1]
    rem = (-k) % chunk
    if rem:
        xq = jnp.pad(xq, ((0, 0), (0, rem)))
        wq = jnp.pad(wq, ((0, rem), (0, 0)))
    nk = xq.shape[1] // chunk
    x_chunks = xq.astype(jnp.int32).reshape(m, nk, chunk).transpose(1, 0, 2)
    w_chunks = wq.astype(jnp.int32).reshape(nk, chunk, n)

    def step(acc, xw):
        xc, wc = xw
        acc = acc + jax.lax.dot_general(xc, wc, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.int32)
        return acc, None

    acc, _ = jax.lax.scan(step, jnp.zeros((m, n), jnp.int32),
                          (x_chunks, w_chunks))
    return acc


def int_accumulate_pallas(xq: jax.Array, wq: jax.Array,
                          interpret: bool = True) -> jax.Array:
    """Int32 accumulate through the Pallas kernel (unit scales make the f32
    output the raw accumulate; exact for |acc| < 2^24, i.e. K <= 1040 at
    8 bits — every ViT shape in this repo)."""
    from repro.kernels.ops import pad_to
    from repro.kernels.photonic_matmul import photonic_matmul_int8

    m, k = xq.shape
    n = wq.shape[1]
    xp = pad_to(pad_to(xq, 128, 0), 128, 1)
    wp = pad_to(pad_to(wq, 128, 0), 128, 1)
    out = photonic_matmul_int8(xp, wp, jnp.float32(1.0),
                               jnp.ones((wp.shape[1],), jnp.float32),
                               interpret=interpret)
    return out[:m, :n].astype(jnp.int32)


# --------------------------------------------------------------------------
# backend registry
# --------------------------------------------------------------------------

BACKENDS: dict[str, Callable] = {}


def register_backend(name: str):
    def deco(fn):
        BACKENDS[name] = fn
        return fn
    return deco


def get_backend(name: str) -> Callable:
    try:
        return BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown matmul backend {name!r}; "
                       f"available: {available_backends()}") from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(BACKENDS))


@register_backend("bf16")
def _bf16_matmul(x, w, p: ExecPolicy):
    """Plain MXU dot: f32 accumulate (or operand-dtype out, §Perf knob)."""
    if isinstance(w, QuantizedWeight):
        w = w.dequantize().astype(x.dtype)
    if p.dot_out_native:
        return jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())))
    return jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)


@register_backend("qat")
def _qat_matmul(x, w, p: ExecPolicy):
    """QAT fake-quant: weights per-out-channel + activations per-tensor,
    STE in training so gradients flow (paper §IV Accuracy Analysis)."""
    bits = p.quant_bits or 8
    fq = quant.fake_quant_ste if p.training else quant.fake_quant
    if isinstance(w, QuantizedWeight):
        wq = w.dequantize().astype(x.dtype)     # cache already quantized it
    else:
        wq = fq(w, bits=bits, axis=tuple(range(w.ndim - 1)))
    xq = fq(x, bits=bits, axis=None)
    return jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32
                               ).astype(x.dtype)


def _photonic_prologue(x, w, p: ExecPolicy):
    bits = _weight_bits(w, p)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    sx = quant.absmax_scale(x2, bits=bits)
    xq = quant.quantize(x2, sx, bits=bits)
    wq, sw = _resolve_wq(w, bits)
    return lead, xq, wq, sx, sw


@register_backend("photonic_sim")
def _photonic_sim_matmul(x, w, p: ExecPolicy):
    """Chunk-walking w8a8 oracle: integer accumulate over 32-wavelength
    K-chunks, then the dequant epilogue (ADC + scale restore)."""
    lead, xq, wq, sx, sw = _photonic_prologue(x, w, p)
    acc = int_accumulate_sim(xq, wq)
    y = acc.astype(jnp.float32) * sx * sw.reshape(1, -1)
    return y.reshape(*lead, _out_dim(w)).astype(x.dtype)


@register_backend("photonic_pallas")
def _photonic_pallas_matmul(x, w, p: ExecPolicy):
    """Int8 Pallas MXU kernel (interpret=True on CPU hosts). With a cached
    ``QuantizedWeight`` only the activations are quantized per call."""
    from repro.kernels import ops as kernel_ops   # lazy: pulls in pallas

    bits = _weight_bits(w, p)
    wq, sw = _resolve_wq(w, bits)
    y = kernel_ops.photonic_matmul_prequant(
        x.astype(jnp.float32), wq, sw.reshape(-1), bits=bits,
        interpret=p.interpret)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# calibrated device-noise dispatch (ExecPolicy.noise)
# --------------------------------------------------------------------------

def _noisy_matmul(x, w, p: ExecPolicy):
    """One noisy path for every backend — the registry entries stay the
    clean contract and the dispatch is not forked per backend.

    Weight-stationary MR banks take the transmission error (crosstalk floor
    + FPV + Lorentzian drift/wander, core/noise.py); the readout takes shot
    noise and an optional range-limited ADC. Photonic backends walk the
    analog float-code schedule (sub-LSB noise cannot ride through int8
    codes); bf16/qat apply the multiplier to their effective float weight.
    Keys come from the active noise scope — a DriftState installed by the
    serving entry points via ``noise.scoped`` — so successive frames draw
    fresh patterns and a pinned state reproduces bitwise.
    """
    from repro.core import noise as noise_mod

    spec = p.noise
    kc, kf, drift = noise_mod.next_call_keys(spec)
    backend = p.resolve_backend()
    mr = spec.mr()

    def _mult(shape):
        return noise_mod.transmission_error(
            kc, shape, mr, spec.fpv_sigma, fpv_key=kf, drift_nm=drift,
            wander_sigma_nm=spec.wander_sigma_nm)

    if backend.startswith("photonic"):
        bits = _weight_bits(w, p)
        wq, sw = _resolve_wq(w, bits)
        mult = _mult(wq.shape)
        if backend == "photonic_pallas":
            from repro.kernels import ops as kernel_ops
            y = kernel_ops.photonic_matmul_prequant_noisy(
                x.astype(jnp.float32), wq, sw.reshape(-1), mult,
                noise_mod.shot_key(kc), bits=bits,
                shot_sigma=spec.shot_sigma,
                adc_bits=bits if spec.adc_quantize_output else 0,
                chunk=_WAVELENGTHS)
            return y.astype(x.dtype)
        from repro.core.photonic import analog_accumulate
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        sx = quant.absmax_scale(x2, bits=bits)
        xq = quant.quantize(x2, sx, bits=bits)
        acc = analog_accumulate(xq, wq.astype(jnp.float32) * mult,
                                chunk=_WAVELENGTHS)
        y = acc * sx * sw.reshape(1, -1)
        y = noise_mod.readout_noise(y, spec, kc, bits=bits)
        return y.reshape(*lead, _out_dim(w)).astype(x.dtype)

    # bf16 / qat: transmission error on the effective float weight
    bits = p.quant_bits or 8
    if isinstance(w, QuantizedWeight):
        wf = w.dequantize()
        xf = x.astype(jnp.float32)
    elif backend == "qat":
        fq = quant.fake_quant_ste if p.training else quant.fake_quant
        wf = fq(w.astype(jnp.float32), bits=bits,
                axis=tuple(range(w.ndim - 1)))
        xf = fq(x.astype(jnp.float32), bits=bits, axis=None)
    else:
        wf = w.astype(jnp.float32)
        xf = x.astype(jnp.float32)
    y = jax.lax.dot_general(xf, wf * _mult(wf.shape),
                            (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = noise_mod.readout_noise(y, spec, kc, bits=bits)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# the single matmul entry point
# --------------------------------------------------------------------------

def matmul(x: jnp.ndarray, w, policy: ExecPolicy | None = None) -> jnp.ndarray:
    """y = x @ w under the active execution policy.

    x: (..., d_in); w: (d_in, d_out) array or cached ``QuantizedWeight``.
    """
    p = policy or ExecPolicy()
    if p.noise is not None:
        return _noisy_matmul(x, w, p)
    return get_backend(p.resolve_backend())(x, w, p)


def linear(x: jnp.ndarray, w, b: jnp.ndarray | None = None,
           policy: ExecPolicy | None = None) -> jnp.ndarray:
    """y = x @ w (+ b) under the active execution policy (see ``matmul``)."""
    y = matmul(x, w, policy)
    if b is not None:
        y = y + b
    return y


# --------------------------------------------------------------------------
# attention-core registry (score -> softmax -> PV under one dispatch point)
# --------------------------------------------------------------------------

ATTN_BACKENDS: dict[str, Callable] = {}


def register_attention_backend(name: str):
    def deco(fn):
        ATTN_BACKENDS[name] = fn
        return fn
    return deco


def get_attention_backend(name: str) -> Callable:
    try:
        return ATTN_BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown attention backend {name!r}; "
                       f"available: {available_attention_backends()}") from None


def available_attention_backends() -> tuple[str, ...]:
    return tuple(sorted(ATTN_BACKENDS))


@register_attention_backend("xla")
def _attend_xla(q, k, v, p: ExecPolicy, mask, kv_len, scale):
    """Materialized-score reference dataflow: the full (Sq, Skv) score
    matrix is computed, masked keys get a large negative additive bias
    (softmax assigns them exactly-zero weight — the serving parity
    contract), then softmax @ V. Runs in the operands' dtype, exactly the
    pre-registry mhsa numerics. A packed ``kv_len`` is expressed as a
    prefix mask — this backend is the post-hoc reference, it never skips."""
    from repro.kernels.ref import (expand_kv_heads,   # pure jnp, no pallas
                                   prefix_key_mask)

    h = q.shape[-3]
    if kv_len is not None:
        mask = prefix_key_mask(kv_len, 1, k.shape[-2])[0]
    s = (q @ jnp.swapaxes(expand_kv_heads(k, h), -1, -2)) * scale
    if mask is not None:
        s = s + ((mask.astype(jnp.float32) - 1.0) * 1e9
                 ).astype(s.dtype)[..., None, None, :]
    probs = jax.nn.softmax(s, axis=-1)
    o = probs @ expand_kv_heads(v, h)
    if mask is not None:
        # rows with zero live keys output exactly 0, not the uniform
        # average softmax degenerates to — the flash/oracle contract
        o = o * (mask.sum(-1) > 0)[..., None, None, None].astype(o.dtype)
    return o


@register_attention_backend("flash")
def _attend_flash(q, k, v, p: ExecPolicy, mask, kv_len, scale):
    """Fused RoI-masked flash dataflow: streaming softmax in VMEM, masked
    keys applied inside the update, fully-pruned KV blocks skipped — on
    TPU (``interpret=False``) the Pallas kernel; on CPU hosts the XLA
    lowering of the same contract (kernels/flash_attention.py). A static
    ``kv_len`` takes the packed-skip path: the dead KV tail costs zero
    score FLOPs."""
    from repro.kernels.flash_attention import fused_masked_attention

    lead = q.shape[:-3]
    b = 1
    for n in lead:
        b *= n
    h, sq, d = q.shape[-3:]
    qf = q.reshape(b, h, sq, d)
    kf = k.reshape((b,) + k.shape[-3:])
    vf = v.reshape((b,) + v.shape[-3:])
    mf = None
    if mask is not None:
        # accept the same lead-dim-elided masks the xla backend broadcasts
        mf = jnp.broadcast_to(mask, lead + mask.shape[-1:]).reshape(
            b, mask.shape[-1])
    out = fused_masked_attention(qf, kf, vf, mf, kv_len=kv_len, scale=scale,
                                 interpret=p.interpret)
    return out.reshape(*lead, h, sq, vf.shape[-1])


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           policy: ExecPolicy | None = None, *,
           mask: jnp.ndarray | None = None,
           kv_len: int | None = None,
           scale: float | None = None) -> jnp.ndarray:
    """softmax(q @ k^T * scale + key-mask bias) @ v under the active policy.

    q (..., H, Sq, D); k (..., Hk, Skv, D); v (..., Hv, Skv, Dv) ->
    (..., H, Sq, Dv); H a multiple of Hk and Hv. ``mask`` (..., Skv) is a
    {0,1} keep-mask on the key axis (RoI mask mode); ``kv_len`` is the
    packed alternative (key j kept iff j < kv_len — the one-shape serving
    layout; a static int lets the flash backend drop the dead tail before
    any score FLOP). Give at most one. ``scale`` defaults to 1/sqrt(D) —
    pass 1.0 when it is already folded into q (Eq. 2). The score and PV
    products are activation-activation matmuls (dynamically tuned cores on
    the photonic hardware), so they stay float on every matmul backend;
    only *which dataflow computes them* is dispatched here.
    """
    p = policy or ExecPolicy()
    if mask is not None and kv_len is not None:
        raise ValueError("give mask or kv_len, not both")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return get_attention_backend(p.resolve_attn_backend())(q, k, v, p,
                                                           mask, kv_len,
                                                           scale)


# --------------------------------------------------------------------------
# FFN registry (w1 -> GELU -> w2 under one dispatch point)
# --------------------------------------------------------------------------

FFN_BACKENDS: dict[str, Callable] = {}


def register_ffn_backend(name: str):
    def deco(fn):
        FFN_BACKENDS[name] = fn
        return fn
    return deco


def get_ffn_backend(name: str) -> Callable:
    try:
        return FFN_BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown ffn backend {name!r}; "
                       f"available: {available_ffn_backends()}") from None


def available_ffn_backends() -> tuple[str, ...]:
    return tuple(sorted(FFN_BACKENDS))


@register_ffn_backend("xla")
def _ffn_xla(x, w1, b1, w2, b2, p: ExecPolicy, live_rows):
    """Composed reference dataflow: two independent ``linear`` dispatches
    with the float GELU round-trip between them — the hidden (B, S, d_ff)
    activation crosses the dispatch boundary at float precision twice.
    Runs on every matmul backend; exactly the pre-registry mlp numerics.
    ``live_rows`` is ignored — this backend is the post-hoc reference, it
    never skips (the same contract as the xla attention backend)."""
    from repro.distributed.sharding import shard   # lazy: keeps core free
    #                                                of a launch-layer dep
    h = linear(x, w1, b1, policy=p)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    h = shard(h, "batch", "seq", "mlp")
    return linear(h, w2, b2, policy=p)


def _fused_ffn_ineligible_reason(w1, w2, p: ExecPolicy) -> str | None:
    """None when the block can take the fused int8 FFN kernel — int8
    Pallas matmul backend + both weights quantize-once cached at (possibly
    different) <= 8-bit widths — else a human-readable reason (mirrors
    ``_fused_prequant_eligible`` for the MHSA block). w1 and w2 may carry
    *different* widths: the kernel quantizes the input at w1's width and
    requantizes the hidden state at w2's, exactly the composed numerics."""
    if p.noise is not None:
        return ("calibrated device noise is active (ExecPolicy.noise) — "
                "the fused int8 kernel is the clean digital contract; "
                "noisy execution runs the composed analog dispatch")
    if p.resolve_backend() != "photonic_pallas":
        return (f"matmul backend is {p.resolve_backend()!r}, fused kernel "
                f"needs 'photonic_pallas'")
    if not (isinstance(w1, QuantizedWeight) and isinstance(w2, QuantizedWeight)):
        return "w1/w2 not quantize-once cached (run prepare_params)"
    if not (w1.ndim == 2 and w2.ndim == 2):
        return "w1/w2 still scan-stacked (ndim > 2), not per-layer slices"
    if not (isinstance(w1.bits, int) and isinstance(w2.bits, int)):
        return (f"w1/w2 carry stacked per-layer bits ({w1.bits}/{w2.bits}),"
                f" not a single width")
    if not (w1.bits <= 8 and w2.bits <= 8):
        return f"bit widths ({w1.bits}, {w2.bits}) above the int8 kernel max"
    return None


def _fused_ffn_eligible(w1, w2, p: ExecPolicy) -> bool:
    return _fused_ffn_ineligible_reason(w1, w2, p) is None


@register_ffn_backend("fused")
def _ffn_fused(x, w1, b1, w2, b2, p: ExecPolicy, live_rows):
    """Fused int8 photonic FFN (kernels/fused_ffn.py): both matmuls, the
    bias adds, the GELU and the hidden requantization run in one kernel
    over the cached weight tiles, the hidden state staying in VMEM. A
    static ``live_rows`` (one-shape serving mode) drops fully-pruned
    token rows before any FLOP, returning exact zeros for them (activation
    scales then reduce over live rows only — the packed-skip contract).
    w1 and w2 may be cached at different widths (a mixed-precision bit
    plan): the input is quantized at w1's width, the hidden state
    requantized at w2's — bit-identical to the composed two-``linear``
    dispatch under the same cache. Falls back to the composed dispatch
    (with a one-time warning) when the weights are not cached int8 or the
    matmul backend is not the Pallas kernel."""
    reason = _fused_ffn_ineligible_reason(w1, w2, p)
    if reason is not None:
        warn_fused_fallback("FFN", p, reason)
        return _ffn_xla(x, w1, b1, w2, b2, p, live_rows)
    from repro.kernels.fused_ffn import fused_ffn   # lazy: pulls in pallas

    return fused_ffn(x, w1.wq, w1.scale.reshape(-1), b1,
                     w2.wq, w2.scale.reshape(-1), b2,
                     bits=(w1.bits, w2.bits),
                     live_rows=live_rows, interpret=p.interpret)


def ffn(x: jnp.ndarray, w1, b1: jnp.ndarray, w2, b2: jnp.ndarray,
        policy: ExecPolicy | None = None, *,
        live_rows: int | None = None) -> jnp.ndarray:
    """y = gelu(x @ w1 + b1) @ w2 + b2 under the active execution policy.

    x (..., n, d_in); w1 (d_in, d_ff) / w2 (d_ff, d_out) raw arrays or
    cached ``QuantizedWeight``s. ``live_rows`` statically prunes the token
    axis on skipping backends (key j live iff j < live_rows — the packed
    one-shape serving layout); the xla reference computes every row.
    """
    p = policy or ExecPolicy()
    return get_ffn_backend(p.resolve_ffn_backend())(x, w1, b1, w2, b2, p,
                                                    live_rows)
