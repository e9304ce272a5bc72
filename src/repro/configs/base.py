"""Architecture + shape configuration system.

One ``ArchConfig`` dataclass describes every supported architecture family:
  dense   - decoder-only transformer (GQA, optional QKV bias)
  moe     - dense attention + top-k routed expert FFNs (optional shared)
  ssm     - Mamba-2 SSD (attention-free)
  hybrid  - RG-LRU recurrence + periodic local attention (RecurrentGemma)
  encdec  - encoder-decoder with cross-attention (Whisper; conv frontend stub)
  vlm     - decoder with periodic image cross-attention (Llama-3.2-Vision;
            vision tower stub supplies patch embeddings)
  vit     - vision transformer (the paper's own backbone, MGNet-aware)

``ShapeConfig`` describes one benchmark cell (seq_len x global_batch x kind).
Shape kinds: "train" lowers train_step; "prefill" lowers a forward pass;
"decode" lowers serve_step (one token against a KV/state cache of seq_len).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

__all__ = ["ArchConfig", "ShapeConfig", "SHAPES", "smoke_variant"]


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | encdec | vlm | vit
    n_layers: int
    d_model: int
    n_heads: int
    kv_heads: int
    d_ff: int
    vocab: int

    # attention
    qkv_bias: bool = False
    rope_theta: float = 500000.0
    attn_impl: str = "standard"          # standard | decomposed
    window: int = 0                      # local-attention window (hybrid)
    attn_every: int = 0                  # hybrid: attn layer every k-th layer
    attn_block_q: int = 512              # blockwise-attention tile sizes
    attn_block_kv: int = 1024
    causal_block_skip: bool = False      # skip fully-masked KV blocks (perf opt)

    # MoE
    n_experts: int = 0
    top_k: int = 0
    shared_experts: int = 0
    capacity_factor: float = 1.25
    first_dense_layers: int = 0          # e.g. Kimi-K2 keeps layer 0 dense
    moe_groups: int = 1                  # dispatch groups (= batch shards)

    # SSM (Mamba-2 SSD)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    conv_kernel: int = 4
    ssm_chunk: int = 256

    # hybrid (RG-LRU)
    lru_width: int = 0                   # 0 -> d_model

    # enc-dec / vlm stubs
    enc_layers: int = 0
    enc_frames: int = 1500               # whisper frontend stub output length
    cross_every: int = 0                 # vlm: cross-attn every k-th layer
    n_img_tokens: int = 0
    d_frontend: int = 0                  # stub embedding dim (0 -> d_model)

    # vit / paper-specific
    img_size: int = 224
    patch: int = 16
    mgnet: bool = False
    mgnet_keep_ratio: float = 1.0
    mgnet_embed: int = 192        # paper: 192/3 classification, 384/6 det.
    mgnet_heads: int = 3

    # training & memory policy
    remat: bool = True
    scan_layers: bool = True
    microbatch_steps: int = 1            # gradient-accumulation steps
    use_fp32_master: bool = False        # 405B-scale keeps optimizer in bf16
    lr_warmup: int = 100                 # warmup steps (schedule knob)
    lr_total: int = 10000                # cosine-decay horizon

    # paper technique knobs
    quant_bits: int = 0                  # 0 = off; 8 = paper's QAT/photonic
    photonic: bool = False
    matmul_backend: str = ""             # "" = resolve from the flags above;
    #                                      explicit: bf16 | qat | photonic_sim
    #                                      | photonic_pallas (core/backend.py)
    attn_backend: str = ""               # attention-core dispatch: "" -> xla
    #                                      (materialized scores) | flash
    #                                      (fused RoI-masked Pallas kernel,
    #                                      core/backend.py ATTN_BACKENDS)
    ffn_backend: str = ""                # GELU-MLP dispatch: "" -> xla
    #                                      (composed two-linear) | fused
    #                                      (fused int8 photonic FFN kernel,
    #                                      core/backend.py FFN_BACKENDS)
    bit_plan: tuple = ()                 # per-layer bit widths (one per
    #                                      encoder block, core/bitalloc.py);
    #                                      () = uniform quant_bits. Feeds
    #                                      prepare_params(bit_plan=...) and
    #                                      ExecPolicy.bit_plan
    noise: object = None                 # calibrated device-noise operating
    #                                      point (core/noise.py NoiseSpec,
    #                                      a frozen/hashable dataclass) or
    #                                      None = clean. Feeds
    #                                      ExecPolicy.noise; typed loosely
    #                                      to keep configs import-light

    # perf-hillclimb knobs (EXPERIMENTS.md §Perf; all default to the
    # paper-faithful baseline behaviour)
    dot_out_native: bool = False   # dot outputs in operand dtype (bf16) —
    #                                halves TP-activation all-reduce + dot
    #                                output traffic (MXU still accumulates
    #                                f32 internally)
    attn_p_bf16: bool = False      # softmax probs + V in bf16 inside the
    #                                flash PV matmul (f32 running stats)
    attn_qk_bf16: bool = False     # QK score dot reads bf16 operands
    #                                (f32 accumulate) — halves Q/K traffic
    decode_attn_bf16: bool = False  # decode attention reads the KV cache
    #                                in bf16 (f32 accumulate/softmax) —
    #                                without it XLA materializes an f32
    #                                copy of the whole cache per layer
    grad_accum_dtype: str = "f32"  # microbatch grad accumulator ("bf16"
    #                                halves accumulator memory at 1T scale)
    moe_local_combine: bool = False  # reshard expert outputs to
    #                                group-local before the combine gather
    #                                (all-gather instead of GSPMD's masked
    #                                all-reduce fallback)
    moe_impl: str = "gspmd"        # "gspmd" | "shard_map" (explicit EP:
    #                                communication-free dispatch + partial
    #                                combine psum — the full §Perf fix)

    norm_eps: float = 1e-6
    tie_embeddings: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:            # ssm inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def lru_dim(self) -> int:
        return self.lru_width or self.d_model

    def with_(self, **kw) -> "ArchConfig":
        return replace(self, **kw)

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6*N*D)."""
        d, v, L = self.d_model, self.vocab, self.n_layers
        n = v * d                                     # token embedding
        if not self.tie_embeddings:
            n += v * d                                # lm head
        hd = self.head_dim

        def attn_params():
            return (d * self.n_heads * hd + 2 * d * self.kv_heads * hd
                    + self.n_heads * hd * d)

        def ffn_dense(dff):
            return 3 * d * dff                        # SwiGLU: w1, w3, w2

        if self.family == "ssm":
            di = self.d_inner
            per = (d * (2 * di + 2 * self.ssm_state * 1 + self.ssm_heads)  # in_proj(z,x,B,C,dt)
                   + di * d                            # out_proj
                   + self.conv_kernel * (di + 2 * self.ssm_state))
            n += L * per
        elif self.family == "hybrid":
            lru = self.lru_dim
            attn_layers = L // 3 if self.attn_every else 0
            rec_layers = L - attn_layers
            per_rec = d * lru * 2 + lru * d + 2 * lru + self.conv_kernel * lru
            n += rec_layers * per_rec + attn_layers * attn_params()
            n += L * ffn_dense(self.d_ff)
        elif self.family == "moe":
            dense_l = self.first_dense_layers
            moe_l = L - dense_l
            per_moe = (self.n_experts + self.shared_experts) * ffn_dense(self.d_ff) \
                + d * self.n_experts                  # router
            n += L * attn_params() + dense_l * ffn_dense(self.d_ff * self.n_experts
                                                         if False else self.d_ff)
            # dense layers in MoE models use a wide dense FFN comparable to
            # top_k * d_ff activated width
            n += moe_l * per_moe
        elif self.family == "encdec":
            n += self.enc_layers * (attn_params() + ffn_dense(self.d_ff))
            n += L * (2 * attn_params() + ffn_dense(self.d_ff))   # self + cross
        elif self.family == "vlm":
            cross_l = L // self.cross_every if self.cross_every else 0
            n += L * (attn_params() + ffn_dense(self.d_ff))
            n += cross_l * attn_params()
        else:  # dense / vit
            n += L * (attn_params() + ffn_dense(self.d_ff))
        return n

    def active_param_count(self) -> int:
        """Activated params per token (MoE uses 6*N_active*D)."""
        if self.family != "moe":
            return self.param_count()
        d, L = self.d_model, self.n_layers
        hd = self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.kv_heads * hd + self.n_heads * hd * d
        ffn_active = (self.top_k + self.shared_experts) * 3 * d * self.d_ff
        moe_l = L - self.first_dense_layers
        n = 2 * self.vocab * d
        n += L * attn + self.first_dense_layers * 3 * d * self.d_ff
        n += moe_l * (ffn_active + d * self.n_experts)
        return n


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def smoke_variant(cfg: ArchConfig) -> ArchConfig:
    """Tiny same-family config for CPU smoke tests (one fwd/train step)."""
    kw = dict(
        n_layers=min(cfg.n_layers, 4) if cfg.family != "hybrid" else 3,
        d_model=64,
        n_heads=4,
        kv_heads=min(cfg.kv_heads, 2),
        d_ff=128,
        vocab=256,
        microbatch_steps=1,
        remat=False,
    )
    if cfg.family == "moe":
        kw.update(n_experts=4, top_k=2, shared_experts=min(cfg.shared_experts, 1),
                  first_dense_layers=min(cfg.first_dense_layers, 1), d_ff=64)
    if cfg.family == "ssm":
        kw.update(ssm_state=16, ssm_headdim=16, ssm_chunk=16)
    if cfg.family == "hybrid":
        kw.update(lru_width=64, window=16, attn_every=3)
    if cfg.family == "encdec":
        kw.update(enc_layers=2, enc_frames=8, d_frontend=64)
    if cfg.family == "vlm":
        kw.update(cross_every=2, n_img_tokens=8, d_frontend=64)
    if cfg.family == "vit":
        kw.update(img_size=32, patch=8)
    return cfg.with_(**kw)
