"""Vision Transformer backbone + Opto-ViT integration (the paper's model).

Standard ViT (Dosovitskiy et al.) with the paper's co-design hooks:
  * every matmul (backbone, attention projections, FFN and MGNet) routes
    through ``linear`` -> the backend registry of core/backend.py
    (bf16 | qat | photonic_sim | photonic_pallas, selected by
    ArchConfig.matmul_backend / .quant_bits / .photonic); serve-time params
    can be pre-tuned once with ``core.backend.prepare_params``,
  * every attention core (standard and decomposed, masked and gathered)
    routes through ``attend`` -> the attention registry (xla materialized
    scores | fused RoI-masked flash Pallas kernel, selected by
    ArchConfig.attn_backend); with the int8 Pallas matmul backend + cached
    weights the whole MHSA block takes the one-jit serving hot path,
  * every GELU-MLP routes through ``core.backend.ffn`` -> the FFN registry
    (xla composed two-linear | fused int8 photonic FFN kernel, selected by
    ArchConfig.ffn_backend); in one-shape serving mode the encoder threads
    the static packed live-token count into the FFN so fully-pruned rows
    skip both matmuls, the GELU and the requantization,
  * on the fully-fused serving point (photonic_pallas + flash + fused with
    cached <= 8-bit weights — uniform or a mixed per-layer bit plan)
    ``encode_tokens`` routes through one cached jit: fused attention +
    fused FFN + both residual adds/LayerNorms compose into a single
    jitted per-layer step scanned over the stacked layer weights, mixed
    plans segmenting the stack into equal-bits runs (one scan per run,
    still one jit) — the encoder costs ~one dispatch total instead of ~4
    per layer, computing bit-identical numbers to the composed dispatch,
  * optional Eq. 2 decomposed attention dataflow (attn_impl="decomposed"),
  * optional MGNet RoI pruning: patches are scored by MGNet and only the
    top-k (static budget = ceil(keep_ratio * N)) enter encoder block 0 —
    all downstream compute scales linearly with kept patches (the paper's
    central energy lever). The [cls] token is always kept.

Variants (paper Table I): Tiny/Small/Base/Large at 96x96 and 224x224 are
built by ``configs.opto_vit``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import mgnet as mgnet_mod
from repro.core import noise as noise_mod
from repro.core.decomposed_attention import mhsa_decomposed, mhsa_standard
from repro.core.mgnet import MGNetConfig, mgnet_scores, patchify
from repro.distributed.sharding import current_ctx, shard
from repro.models import ffn as ffn_mod
from repro.models.layers import (ExecPolicy, QuantizedWeight, he_init,
                                 layernorm, linear)

__all__ = ["init_vit", "vit_logical_axes", "forward_vit", "embed_patches",
           "encode_tokens", "encoder_layer_step", "forward_vit_tokens",
           "forward_vit_masked", "vit_matmul_shapes"]


def _n_patches(cfg):
    return (cfg.img_size // cfg.patch) ** 2


def init_vit(key, cfg: ArchConfig, n_classes: int = 1000,
             dtype=jnp.float32) -> dict:
    d = cfg.d_model
    n_in = 3 * cfg.patch ** 2
    ks = jax.random.split(key, 6)

    def layer(k):
        kk = jax.random.split(k, 5)
        return {
            "ln1_g": jnp.ones((d,), dtype), "ln1_b": jnp.zeros((d,), dtype),
            "attn": {"wq": he_init(kk[0], (d, d), dtype),
                     "wk": he_init(kk[1], (d, d), dtype),
                     "wv": he_init(kk[2], (d, d), dtype),
                     "wo": he_init(kk[3], (d, d), dtype)},
            "ln2_g": jnp.ones((d,), dtype), "ln2_b": jnp.zeros((d,), dtype),
            "ffn": ffn_mod.init_mlp(kk[4], d, cfg.d_ff, dtype),
        }

    params = {
        "patch_embed": {"w": he_init(ks[0], (n_in, d), dtype),
                        "b": jnp.zeros((d,), dtype)},
        "cls": (jax.random.normal(ks[1], (1, 1, d), jnp.float32) * 0.02
                ).astype(dtype),
        "pos": (jax.random.normal(ks[2], (1, _n_patches(cfg) + 1, d),
                                  jnp.float32) * 0.02).astype(dtype),
        "blocks": jax.vmap(layer)(jax.random.split(ks[3], cfg.n_layers)),
        "final_ln_g": jnp.ones((d,), dtype),
        "final_ln_b": jnp.zeros((d,), dtype),
        "head": he_init(ks[4], (d, n_classes), dtype),
    }
    if cfg.mgnet:
        mcfg = MGNetConfig(patch=cfg.patch, img_size=cfg.img_size,
                           embed=cfg.mgnet_embed, heads=cfg.mgnet_heads)
        params["mgnet"] = mgnet_mod.init_mgnet(ks[5], mcfg)
    return params


def vit_logical_axes(cfg: ArchConfig) -> dict:
    from repro.models.transformer import _tree_prepend_axis
    layer = {"ln1_g": (None,), "ln1_b": (None,),
             # wq/wk/wv output columns are head-major, so a "model" mesh
             # axis splits them into whole head groups (the sharded
             # encoder's layout — MODEL_RULES maps p_heads there). wo is
             # deliberately NOT tagged p_heads on its (head-major) rows:
             # the sharded encoder consumes it whole after all-gathering
             # the merged head outputs (its dequant runs inside the
             # photonic matmul kernel, so a row split cannot reduce the
             # int32 accumulates before dequant without changing numerics)
             "attn": {"wq": ("p_embed", "p_heads"), "wk": ("p_embed", "p_heads"),
                      "wv": ("p_embed", "p_heads"), "wo": (None, "p_embed")},
             "ln2_g": (None,), "ln2_b": (None,),
             "ffn": ffn_mod.mlp_logical_axes()}
    ax = {"patch_embed": {"w": (None, "p_embed"), "b": ("p_embed",)},
          "cls": (None, None, None), "pos": (None, None, None),
          "blocks": _tree_prepend_axis(layer),
          "final_ln_g": (None,), "final_ln_b": (None,),
          "head": ("p_embed", None)}
    if cfg.mgnet:
        # structure-matching all-None (replicated) tree — an empty pytree
        # here would break annotation tree_maps against the real params.
        ax["mgnet"] = mgnet_mod.mgnet_logical_axes()
    return ax


def embed_patches(params: dict, images: jnp.ndarray, cfg: ArchConfig,
                  policy: ExecPolicy | None = None) -> jnp.ndarray:
    """images (B, H, W, 3) -> position-embedded patch tokens (B, N, d).

    The serving engine calls this once per ingested frame chunk, then
    gathers per-frame top-k subsets (bucket routing) — positional
    information must therefore already live in the tokens, which is why the
    pos table is added *before* any pruning (identical to the fused path).
    """
    policy = policy or ExecPolicy.from_cfg(cfg)
    pt = patchify(images, cfg.patch)                      # (B, N, p*p*3)
    x = linear(pt, params["patch_embed"]["w"], params["patch_embed"]["b"],
               policy)
    return x + params["pos"][:, 1: x.shape[1] + 1]


def encoder_layer_step(carry: jnp.ndarray, lp: dict, cfg: ArchConfig,
                       policy: ExecPolicy,
                       mask: jnp.ndarray | None = None,
                       attn_kv: int | None = None,
                       ffn_live: int | None = None) -> jnp.ndarray:
    """One encoder layer: LN -> MHSA -> residual -> LN -> FFN -> residual.

    ``lp`` is one layer's param slice (what ``lax.scan`` hands the body).
    On the fully-fused serving point this whole step is two kernel entries
    (``fused_roi_attention_prequant`` + the fused FFN) plus the norms and
    residual adds; ``ffn_live`` threads the packed live-row count so the
    fused FFN skips dead token rows the same way the flash kernel skips
    pruned KV blocks.
    """
    h = layernorm(carry, lp["ln1_g"], lp["ln1_b"], cfg.norm_eps)
    if cfg.attn_impl == "decomposed":
        o = mhsa_decomposed(h, lp["attn"], cfg.n_heads, policy, mask,
                            attn_kv)
    else:
        o = mhsa_standard(h, lp["attn"], cfg.n_heads, policy, mask,
                          attn_kv)
    carry = carry + o.astype(carry.dtype)
    h2 = layernorm(carry, lp["ln2_g"], lp["ln2_b"], cfg.norm_eps)
    return carry + ffn_mod.mlp(lp["ffn"], h2, policy, live_rows=ffn_live)


def _is_qw(a) -> bool:
    return isinstance(a, QuantizedWeight)


def _blocks_qw_leaves(blocks) -> list:
    return [a for a in jax.tree_util.tree_leaves(blocks, is_leaf=_is_qw)
            if _is_qw(a)]


def _blocks_bits_key(blocks) -> tuple:
    """Hashable per-leaf bits signature of the stacked blocks' cache —
    jit-cache key material alongside ``ExecPolicy.fingerprint()`` (the
    params treedef changing would retrace anyway; keying explicitly keeps
    one wrapper per plan in ``_FUSED_ENCODER_JITS``)."""
    return tuple(a.bits for a in _blocks_qw_leaves(blocks))


def _bit_segments(blocks, n_layers: int) -> list[tuple[int, int]]:
    """[lo, hi) runs of consecutive layers whose cached widths agree on
    every QuantizedWeight leaf — the units the segmented scan compiles.
    Uniform caches (every ``bits`` an int) are one run: today's path."""
    leaves = _blocks_qw_leaves(blocks)
    if not any(isinstance(a.bits, tuple) for a in leaves):
        return [(0, n_layers)]
    sig = [tuple(a.layer_bits(i) for a in leaves) for i in range(n_layers)]
    segs, lo = [], 0
    for i in range(1, n_layers + 1):
        if i == n_layers or sig[i] != sig[lo]:
            segs.append((lo, i))
            lo = i
    return segs


def _slice_blocks(blocks, lo: int, hi: int):
    """Layer-range slice of the stacked blocks. QuantizedWeight leaves
    keep codes/scales stacked but collapse ``bits`` to the run's single
    int width — what makes every 2-D in-scan slice carry the int the
    fused kernels and ``_weight_bits`` require."""
    def sl(a):
        if _is_qw(a):
            return QuantizedWeight(a.wq[lo:hi], a.scale[lo:hi],
                                   a.layer_bits(lo))
        return a[lo:hi]
    return jax.tree_util.tree_map(sl, blocks, is_leaf=_is_qw)


def _encode_tokens_impl(params: dict, tokens: jnp.ndarray, cfg: ArchConfig,
                        policy: ExecPolicy,
                        patch_mask: jnp.ndarray | None,
                        kv_len: int | None) -> jnp.ndarray:
    b, _, d = tokens.shape
    cls = jnp.broadcast_to(params["cls"], (b, 1, d)) + params["pos"][:, :1]
    x = jnp.concatenate([cls.astype(tokens.dtype), tokens], axis=1)
    x = shard(x, "batch", "seq", "embed")
    mask = None
    if patch_mask is not None:
        mask = jnp.concatenate(
            [jnp.ones((b, 1), patch_mask.dtype), patch_mask], axis=1)
    attn_kv = None if kv_len is None else int(kv_len) + 1   # + live [cls]
    # the packed live-row hint for skipping FFN backends: in one-shape
    # mode the first kv_len patch rows (+ cls) are the only live ones —
    # the same static count the flash attention backend skips with
    ffn_live = attn_kv

    noisy = policy.noise is not None

    def body(carry, lp):
        return encoder_layer_step(carry, lp, cfg, policy, mask, attn_kv,
                                  ffn_live), None

    def body_noisy(carry, lp_li):
        # the scan shares ONE traced body across layers, so without a
        # per-layer salt every layer would observe the same noise draws
        # (the frozen-pattern bug at scan granularity); folding the
        # scanned layer index into the scope keys decorrelates them
        lp, li = lp_li
        with noise_mod.scope_salt(li):
            return encoder_layer_step(carry, lp, cfg, policy, mask,
                                      attn_kv, ffn_live), None

    fn = jax.checkpoint(body_noisy if noisy else body) if cfg.remat \
        else (body_noisy if noisy else body)
    # segmented scan: runs of equal per-layer bit signature each scan as
    # one unit, so a mixed-precision plan still traces a handful of scans
    # inside ONE jit (uniform caches segment to today's single scan).
    # lax.scan slices the stacked leaves exactly like the [lo:hi] slicing
    # here, so the segmented walk is bitwise equal to the unrolled loop.
    for lo, hi in _bit_segments(params["blocks"], cfg.n_layers):
        seg = (params["blocks"] if (lo, hi) == (0, cfg.n_layers)
               else _slice_blocks(params["blocks"], lo, hi))
        if noisy:
            # xs gains the global layer index ONLY under noise, so the
            # clean graph (and its bitwise contract) is untouched
            x, _ = jax.lax.scan(fn, x, (seg, jnp.arange(lo, hi)))
        else:
            x, _ = jax.lax.scan(fn, x, seg)
    x = layernorm(x, params["final_ln_g"], params["final_ln_b"], cfg.norm_eps)
    return linear(x[:, 0], params["head"], policy=policy)


def _fused_encoder_ineligible_reason(params: dict, cfg: ArchConfig,
                                     policy: ExecPolicy) -> str | None:
    """None when the whole encoder can take the single-jit serving hot
    path — int8 Pallas matmuls + flash attention + fused FFN, standard
    dataflow, every per-layer matmul weight quantize-once cached at <= 8
    bits (uniform *or* a mixed per-layer plan: the segmented scan slices
    mixed stacks into equal-bits runs before the fused entries see them)
    — else a human-readable reason for the composed fallback."""
    if policy.noise is not None:
        return ("calibrated device noise is active (ExecPolicy.noise) — "
                "the fused single-jit encoder is the clean digital "
                "contract; noisy execution runs the composed analog "
                "dispatch")
    if not (policy.resolve_backend() == "photonic_pallas"
            and policy.resolve_attn_backend() == "flash"
            and policy.resolve_ffn_backend() == "fused"):
        return (f"backends ({policy.resolve_backend()!r}, "
                f"{policy.resolve_attn_backend()!r}, "
                f"{policy.resolve_ffn_backend()!r}) are not the fused "
                f"serving triple ('photonic_pallas', 'flash', 'fused')")
    if cfg.attn_impl != "standard":
        return f"attn_impl {cfg.attn_impl!r} (fused path needs 'standard')"
    blocks = params.get("blocks")
    if not isinstance(blocks, dict):
        return "params['blocks'] missing or not a dict"
    try:
        ws = ([blocks["attn"][n] for n in ("wq", "wk", "wv")]
              + [blocks["ffn"][n] for n in ("w1", "w2")])
    except (KeyError, TypeError):
        return "blocks missing attn/ffn weight entries"
    if not all(isinstance(w, QuantizedWeight) for w in ws):
        return ("block weights not quantize-once cached "
                "(run prepare_params)")
    widths = set()
    for w in ws:
        widths.update(w.bits if isinstance(w.bits, tuple) else (w.bits,))
    if not all(2 <= b <= 8 for b in widths):
        return f"cached bit widths {sorted(widths)} outside [2, 8]"
    return None


def _fused_encoder_eligible(params: dict, cfg: ArchConfig,
                            policy: ExecPolicy) -> bool:
    return _fused_encoder_ineligible_reason(params, cfg, policy) is None


# (cfg, policy fingerprint, blocks bits signature, kv_len, has_mask) ->
# jitted encode entry. The serving engine holds one cfg/policy per stream
# and the ladder is small, so this stays a handful of entries per process.
_FUSED_ENCODER_JITS: dict = {}


def _fused_encoder_jit(cfg: ArchConfig, policy: ExecPolicy, bits_key: tuple,
                       kv_len: int | None, has_mask: bool):
    key = (cfg, policy.fingerprint(), bits_key, kv_len, has_mask)
    fn = _FUSED_ENCODER_JITS.get(key)
    if fn is None:
        fn = jax.jit(lambda p, t, m: _encode_tokens_impl(p, t, cfg, policy,
                                                         m, kv_len))
        _FUSED_ENCODER_JITS[key] = fn
    return fn


def encode_tokens(params: dict, tokens: jnp.ndarray, cfg: ArchConfig,
                  policy: ExecPolicy | None = None,
                  patch_mask: jnp.ndarray | None = None,
                  kv_len: int | None = None) -> jnp.ndarray:
    """Encoder trunk on pre-embedded patch tokens -> logits (B, n_classes).

    tokens: (B, k, d) position-embedded patch tokens (any k <= N — the
    serving buckets call this with k in the ladder); the [cls] token is
    prepended here. ``patch_mask`` (B, k) optionally removes tokens from
    every attention key axis without changing shapes (RoI mask mode; cls is
    always kept). ``kv_len`` is the packed alternative for score-ordered
    tokens (one-shape serving mode): only the first ``kv_len`` patch
    tokens are live, a static count the flash attention backend skips the
    dead tail for — and the fused FFN backend skips those rows' FFN tiles.
    Kept-token activations are identical between a masked dense call and a
    gathered top-k call because attention is the only cross-token operator
    in the trunk.

    On the fully-fused serving point (photonic_pallas + flash + fused, all
    weights cached at <= 8 bits — uniform or a mixed per-layer bit plan)
    the call routes through a cached jit of the whole trunk — fused
    attention + fused FFN + norms/residuals as one jitted per-layer step
    scanned over the stacked layer weights (mixed plans scan each
    equal-bits run), one dispatch total. The jit computes the same graph
    this function traces everywhere else, so serving callers that wrap
    their own jit around it simply inline it. When the policy requests
    the fused point but the params are ineligible, a one-time
    ``UserWarning`` names the reason before the composed fallback runs.
    """
    policy = policy or ExecPolicy.from_cfg(cfg)
    if patch_mask is not None and kv_len is not None:
        raise ValueError("give patch_mask or kv_len, not both")
    reason = _fused_encoder_ineligible_reason(params, cfg, policy)
    if reason is None:
        ctx = current_ctx()
        if ctx is not None and ctx.mesh.size > 1:
            # serving mesh: the sharded twin of the fused jit (same graph
            # under shard_map — bitwise-equal logits; the only form whose
            # Pallas kernels a TPU can run on several chips); ineligible
            # combos warn once and keep the unsharded jit.
            from repro.models import sharded_encoder
            sreason = sharded_encoder.sharded_encode_ineligible_reason(
                params, cfg, policy, ctx)
            if sreason is None:
                return sharded_encoder.sharded_encode(
                    params, tokens, cfg, policy, patch_mask,
                    None if kv_len is None else int(kv_len), ctx)
            from repro.core.backend import warn_fused_fallback
            warn_fused_fallback("sharded encoder", policy, sreason)
        fn = _fused_encoder_jit(cfg, policy,
                                _blocks_bits_key(params["blocks"]),
                                None if kv_len is None else int(kv_len),
                                patch_mask is not None)
        return fn(params, tokens, patch_mask)
    if policy.resolve_ffn_backend() == "fused":
        # the policy asked for the fused serving point: name the cause of
        # the composed-dispatch cliff once (core.backend keys the set)
        from repro.core.backend import warn_fused_fallback
        warn_fused_fallback("encoder", policy, reason)
    return _encode_tokens_impl(params, tokens, cfg, policy, patch_mask,
                               kv_len)


def forward_vit(params: dict, images: jnp.ndarray, cfg: ArchConfig,
                policy: ExecPolicy | None = None):
    """images (B, H, W, 3) -> (logits (B, n_classes), kept_patches int).

    With cfg.mgnet, MGNet scores patches and a static top-k budget of
    ceil(keep_ratio * N) enters the encoder — paper's masked inference.
    """
    policy = policy or ExecPolicy.from_cfg(cfg)
    x = embed_patches(params, images, cfg, policy)
    n = x.shape[1]

    kept = n
    if cfg.mgnet and cfg.mgnet_keep_ratio < 1.0:
        mcfg = MGNetConfig(patch=cfg.patch, img_size=cfg.img_size,
                           embed=cfg.mgnet_embed, heads=cfg.mgnet_heads)
        # MGNet shares the optical cores with the backbone: same policy
        # (modulo the gate's default-clean noise stance — gate_policy).
        scores = mgnet_scores(params["mgnet"], images, mcfg,
                              policy.gate_policy())  # (B, N)
        kept = max(1, int(cfg.mgnet_keep_ratio * n))
        x, _ = mgnet_mod.select_topk_patches(scores, x, kept)

    return encode_tokens(params, x, cfg, policy), kept


def forward_vit_tokens(params: dict, tokens: jnp.ndarray, cfg: ArchConfig,
                       policy: ExecPolicy | None = None,
                       kv_len: int | None = None):
    """Pre-gathered token forward: tokens (B, k, d) -> (logits, kept).

    The serving engine's bucketed encode path — the gate/gather already
    happened upstream (possibly against a *cached* RoI mask), so every call
    at a given bucket size k is shape-static and jit-cache-hits. In
    one-shape mode the engine instead passes all N score-ordered tokens
    plus a static ``kv_len``: one compiled token shape, per-bucket
    kv_len-specialized variants, and the flash attention backend skips the
    pruned tail's score FLOPs.
    """
    kept = tokens.shape[1] if kv_len is None else kv_len
    return encode_tokens(params, tokens, cfg, policy, kv_len=kv_len), kept


def forward_vit_masked(params: dict, images: jnp.ndarray,
                       patch_mask: jnp.ndarray, cfg: ArchConfig,
                       policy: ExecPolicy | None = None):
    """Mask-mode dense forward: all N patches enter the encoder but
    ``patch_mask`` (B, N) removes dropped ones from every attention key
    axis. Compute is *not* reduced — this is the accuracy-study / baseline
    path the bucketed top-k engine is benchmarked against."""
    policy = policy or ExecPolicy.from_cfg(cfg)
    x = embed_patches(params, images, cfg, policy)
    return encode_tokens(params, x, cfg, policy, patch_mask), x.shape[1]


def vit_matmul_shapes(cfg: ArchConfig, kept_patches: int | None = None,
                      include_mgnet: bool = False) -> list[tuple[int, int, int]]:
    """(M, K, N) list of every MatMul in one ViT forward — feeds the
    optical-core energy/latency model (benchmarks/fig8..11).

    kept_patches: post-MGNet token count (None = all patches).
    """
    n = (kept_patches if kept_patches is not None else _n_patches(cfg)) + 1
    d, dff, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    n_in = 3 * cfg.patch ** 2
    shapes = [( _n_patches(cfg) if kept_patches is None else kept_patches,
               n_in, d)]                                    # patch embed
    per_layer = [
        (n, d, d), (n, d, d), (n, d, d),                    # q, k, v
        (n, d, n),                                          # scores (per-head agg)
        (n, n, d),                                          # attn @ v
        (n, d, d),                                          # out proj
        (n, d, dff), (n, dff, d),                           # mlp
    ]
    shapes += per_layer * L
    if include_mgnet:
        mcfg = MGNetConfig(patch=cfg.patch, img_size=cfg.img_size,
                           embed=cfg.mgnet_embed, heads=cfg.mgnet_heads)
        nm = mcfg.n_patches + 1
        dm = mcfg.embed
        shapes += [
            (mcfg.n_patches, 3 * mcfg.patch ** 2, dm),      # mgnet patch embed
            (nm, dm, 3 * dm), (nm, dm, nm), (nm, nm, dm), (nm, dm, dm),
            (nm, dm, 4 * dm), (nm, 4 * dm, dm),
            (1, dm, dm), (mcfg.n_patches, dm, mcfg.n_patches),  # scoring
        ]
    return shapes
