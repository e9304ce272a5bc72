"""Mixture-of-Experts FFN with grouped, sort-based capacity dispatch.

Top-k softmax routing (Switch/GShard lineage). Dispatch is *grouped*
(GShard pattern): tokens are reshaped to (G, T/G) where G matches the
data-parallel shard count, and all routing/sort/capacity logic runs
*within* a group — so under GSPMD every sort/cumsum/scatter is local to a
device and the only cross-device movement is the (G, E, C, d) dispatch
buffer resharding from G-sharded to E-sharded: the expert-parallel
all-to-all, measured in the roofline collective term.

Within a group dispatch is *sort-based* (argsort by expert id + gather) —
no one-hot dispatch einsum, so the FLOP profile stays honest (the one-hot
formulation inflates HLO FLOPs by T*E*C*d, poisoning the roofline).
Over-capacity tokens are dropped (combine weight zero) — GShard semantics
with ``capacity_factor`` slack.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_ctx, shard
from repro.models.layers import ExecPolicy, he_init
from repro.models import ffn as ffn_mod

__all__ = ["init_moe", "moe_ffn", "moe_logical_axes", "moe_ffn_shard_map"]


def init_moe(key, d: int, d_ff: int, n_experts: int, shared_experts: int = 0,
             dtype=jnp.bfloat16) -> dict:
    kr, ke, ks = jax.random.split(key, 3)
    keys = jax.random.split(ke, 3)
    params = {
        "router": he_init(kr, (d, n_experts), jnp.float32),
        # experts stacked on a leading E axis
        "w_gate": he_init(keys[0], (n_experts, d, d_ff), dtype),
        "w_up": he_init(keys[1], (n_experts, d, d_ff), dtype),
        "w_down": he_init(keys[2], (n_experts, d_ff, d), dtype),
    }
    if shared_experts:
        params["shared"] = ffn_mod.init_swiglu(ks, d, d_ff * shared_experts,
                                               dtype)
    return params


def moe_logical_axes(shared_experts: int = 0) -> dict:
    ax = {
        "router": ("p_embed", None),
        "w_gate": ("p_experts", "p_embed", None),
        "w_up": ("p_experts", "p_embed", None),
        "w_down": ("p_experts", None, "p_embed"),
    }
    if shared_experts:
        ax["shared"] = ffn_mod.swiglu_logical_axes()
    return ax


def _dispatch_group(xt, probs, top_k, cap):
    """Single-group sort-based dispatch.

    xt (T, d); probs (T, E). Returns (disp (E, C, d), combine info)."""
    t, d = xt.shape
    e = probs.shape[-1]
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)           # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    flat_expert = expert_idx.reshape(-1)                          # (T*k,)
    flat_token = jnp.repeat(jnp.arange(t), top_k)
    flat_gate = gate_vals.reshape(-1)

    order = jnp.argsort(flat_expert)                              # stable
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    rank = jnp.arange(t * top_k, dtype=jnp.int32)
    first = jnp.full((e,), t * top_k, jnp.int32).at[se].min(rank)
    slot = rank - first[se]
    keep = slot < cap
    dest = jnp.where(keep, se * cap + slot, e * cap)              # drop bucket

    buf = jnp.zeros((e * cap + 1, d), xt.dtype)
    buf = buf.at[dest].set(xt[st])
    disp = buf[: e * cap].reshape(e, cap, d)
    return disp, (st, sg, keep, dest)


def _combine_group(out, info, t, top_k, dtype):
    """out (E, C, d) -> y (T, d) weighted by gates."""
    st, sg, keep, dest = info
    e_cap, d = out.shape[0] * out.shape[1], out.shape[2]
    out_flat = out.reshape(e_cap, d)
    contrib = jnp.where(keep[:, None],
                        out_flat[jnp.clip(dest, 0, e_cap - 1)]
                        * sg[:, None].astype(out.dtype), 0)
    y = jnp.zeros((t, d), jnp.float32).at[st].add(contrib.astype(jnp.float32))
    return y.astype(dtype)


def moe_ffn(params: dict, x: jnp.ndarray, *, top_k: int,
            capacity_factor: float = 1.25, groups: int = 1,
            policy: ExecPolicy | None = None,
            local_combine: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y (B, S, d), aux_loss scalar).

    groups: dispatch-group count; set to the batch-shard count so routing
    stays device-local (launch resolves it from the mesh; 1 for tests).
    aux_loss is the Switch load-balancing loss.

    local_combine (§Perf): reshard the expert outputs from E-sharded back
    to group-local BEFORE the combine gather. Without it GSPMD partitions
    the combine gather against an expert(model)-sharded buffer and falls
    back to a masked full-size all-reduce of the (T*k, d) result — the
    dominant collective in the MoE train cells (verified in the dry-run
    HLO). The explicit reshard lowers to one bf16 all-gather of the
    (E, C, d) slab per group instead.
    """
    b, s, d = x.shape
    e = params["w_gate"].shape[0]
    t = b * s
    g = min(groups, b)
    while b % g:                       # groups must divide batch
        g -= 1
    tg = t // g
    xt = x.reshape(g, tg, d)
    xt = shard(xt, "batch", None, None)

    logits = (xt.astype(jnp.float32)
              @ params["router"].astype(jnp.float32))             # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)

    # load-balance aux (global statistics — scalars, cheap collectives)
    me = probs.mean(axis=(0, 1))
    _, top_idx = jax.lax.top_k(probs, top_k)
    load = jnp.zeros((e,), jnp.float32).at[top_idx.reshape(-1)].add(1.0) \
        / (t * top_k)
    aux = e * jnp.sum(me * load)

    cap = max(int(capacity_factor * tg * top_k / e), 1)

    disp, info = jax.vmap(
        lambda xg, pg: _dispatch_group(xg, pg, top_k, cap))(xt, probs)
    # (G, E, C, d): reshard G-sharded -> E-sharded  == the EP all-to-all
    disp = shard(disp, "batch", "experts", None, None)

    def expert_mm(h, w):               # (G,E,C,a) x (E,a,b) -> (G,E,C,b)
        if jax.default_backend() == "cpu":
            # CPU DotThunk can't execute batched bf16 x bf16 -> f32; smoke
            # tests upcast. TPU keeps bf16 operands on the MXU.
            return jnp.einsum("geca,eab->gecb", h.astype(jnp.float32),
                              w.astype(jnp.float32)).astype(h.dtype)
        return jnp.einsum("geca,eab->gecb", h, w,
                          preferred_element_type=jnp.float32).astype(h.dtype)

    gt = expert_mm(disp, params["w_gate"])
    up = expert_mm(disp, params["w_up"])
    hh = jax.nn.silu(gt.astype(jnp.float32)).astype(x.dtype) * up
    hh = shard(hh, "batch", "experts", None, None)
    out = expert_mm(hh, params["w_down"])                         # (G,E,C,d)
    if local_combine:
        # reverse EP reshard: E back to replicated-within-group so the
        # combine gather below is provably local (one all-gather, no
        # masked all-reduce fallback).
        out = shard(out, "batch", None, None, None)
    else:
        out = shard(out, "batch", "experts", None, None)

    y = jax.vmap(lambda og, ig: _combine_group(og, ig, tg, top_k, x.dtype)
                 )(out, info)
    y = y.reshape(b, s, d)

    if "shared" in params:
        y = y + ffn_mod.swiglu(params["shared"], x, policy)
    return y, aux


# --------------------------------------------------------------------------
# explicit expert-parallel path (shard_map) — §Perf "beyond" optimization
# --------------------------------------------------------------------------

def moe_ffn_shard_map(params: dict, x: jnp.ndarray, *, top_k: int,
                      capacity_factor: float = 1.25,
                      policy: ExecPolicy | None = None
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Manual expert-parallel MoE under jax.shard_map.

    Layout exploited: activations are batch-sharded over the DP axes and
    REPLICATED along "model"; expert weights are expert-sharded over
    "model" (+ FSDP over "data" on d_model). Consequences:

      * dispatch needs NO communication — every model peer holds the same
        local tokens, routes them identically, and slices out the rows of
        the capacity buffer belonging to its own experts;
      * FSDP weight gather is an explicit `all_gather` over "data" (same
        wire bytes GSPMD pays);
      * combine is a PARTIAL combine + `psum` over "model": each shard
        scatters only its local experts' outputs into a (T, d) zero
        buffer; the psum both sums multi-expert contributions and restores
        model-replication. Wire = 2·T·d vs the GSPMD fallback's masked
        all-reduce of the f32 (T·k, d) buffer (k·2x more) or
        `moe_local_combine`'s (E·C, d) all-gather (cf·k/2 x more).

    Falls back to the GSPMD path when no mesh ctx is installed or shapes
    don't divide (smoke tests, odd batches).
    """
    ctx = current_ctx()
    b, s, d = x.shape
    e = params["w_gate"].shape[0]
    if ctx is None:
        return moe_ffn(params, x, top_k=top_k,
                       capacity_factor=capacity_factor, policy=policy)
    mesh = ctx.mesh
    batch_rule = ctx.rules.get("batch")
    batch_axes = (batch_rule,) if isinstance(batch_rule, str) else \
        tuple(batch_rule or ())
    embed_rule = ctx.rules.get("p_embed")      # FSDP axes of the d dim
    embed_axes = (embed_rule,) if isinstance(embed_rule, str) else \
        tuple(embed_rule or ())
    m_sz = mesh.shape.get("model", 1)
    dp_sz = 1
    for a in batch_axes:
        dp_sz *= mesh.shape[a]
    fsdp_sz = 1
    for a in embed_axes:
        fsdp_sz *= mesh.shape[a]
    if (b % dp_sz) or (e % m_sz) or (d % fsdp_sz):
        return moe_ffn(params, x, top_k=top_k,
                       capacity_factor=capacity_factor, policy=policy)

    e_loc = e // m_sz
    t_loc = (b // dp_sz) * s
    cap = max(int(capacity_factor * t_loc * top_k / e), 1)

    def body(x_loc, router, wg, wu, wd):
        bl = x_loc.shape[0]
        xt = x_loc.reshape(t_loc, d)
        logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)

        # Switch aux loss from local stats, averaged over the DP axes
        me = probs.mean(axis=0)
        _, top_idx = jax.lax.top_k(probs, top_k)
        load = jnp.zeros((e,), jnp.float32).at[top_idx.reshape(-1)].add(
            1.0) / (t_loc * top_k)
        aux = e * jnp.sum(me * load)
        for ax in batch_axes:
            aux = jax.lax.pmean(aux, ax)

        disp, (st, sg, keep, dest) = _dispatch_group(xt, probs, top_k, cap)

        # my experts' rows only — dispatch communication-free
        midx = jax.lax.axis_index("model")
        my = jax.lax.dynamic_slice_in_dim(disp, midx * e_loc, e_loc, axis=0)

        # explicit FSDP gather of this shard's expert weights (d_model dim)
        wg_f = jax.lax.all_gather(wg, embed_axes, axis=1, tiled=True)
        wu_f = jax.lax.all_gather(wu, embed_axes, axis=1, tiled=True)
        wd_f = jax.lax.all_gather(wd, embed_axes, axis=2, tiled=True)

        def mm(h, w):
            if jax.default_backend() == "cpu":
                return jnp.einsum("eca,eab->ecb", h.astype(jnp.float32),
                                  w.astype(jnp.float32)).astype(h.dtype)
            return jnp.einsum("eca,eab->ecb", h, w,
                              preferred_element_type=jnp.float32
                              ).astype(h.dtype)

        gt = mm(my, wg_f)
        up = mm(my, wu_f)
        hh = jax.nn.silu(gt.astype(jnp.float32)).astype(x_loc.dtype) * up
        out = mm(hh, wd_f)                       # (E_loc, C, d)

        # partial combine: only slots owned by this shard contribute
        lo = midx * e_loc * cap
        dest_l = dest - lo
        mine = keep & (dest_l >= 0) & (dest_l < e_loc * cap)
        out_flat = out.reshape(e_loc * cap, d)
        contrib = jnp.where(
            mine[:, None],
            out_flat[jnp.clip(dest_l, 0, e_loc * cap - 1)]
            * sg[:, None].astype(out.dtype), 0)
        y = jnp.zeros((t_loc, d), jnp.float32).at[st].add(
            contrib.astype(jnp.float32))
        y = jax.lax.psum(y, "model")             # sum experts + re-replicate
        return y.astype(x_loc.dtype).reshape(bl, s, d), aux

    x_spec = P(batch_rule, None, None)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None),
                  P("model", embed_rule, None), P("model", embed_rule, None),
                  P("model", None, embed_rule)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])

    if "shared" in params:
        y = y + ffn_mod.swiglu(params["shared"], x, policy)
    return y, aux
