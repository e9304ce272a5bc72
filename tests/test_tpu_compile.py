"""The serving kernels compile for a TPU v5e, with no chip attached.

Each test lowers and compiles one program for a *described* ``v5e:2x2``
topology (the TPU compiler ships with jaxlib) and checks that the
compiled HLO holds the Pallas kernels as ``tpu_custom_call``s — so a
kernel the chip's compiler refuses (block tiling, VMEM bounds) fails
here, not on the chip. Interpret mode cannot show this.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and test collection runs in
every worker. The persistent compilation cache is off around these
compiles: a TPU executable written there cannot be read back on a host
without a chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs.opto_vit import get_config
from repro.core.backend import ExecPolicy, prepare_params
from repro.roofline.hlo_analysis import tpu_kernel_counts

B = 4                       # the server's default micro-batch
N = 197                     # 224 px / 16 px patches + [cls]


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(fn, *args) -> dict[str, int]:
    return tpu_kernel_counts(jax.jit(fn).lower(*args).compile().as_text())


def _serving_cfg(variant: str):
    return get_config(variant, 224, mgnet=True).with_(
        matmul_backend="photonic_pallas", attn_backend="flash",
        ffn_backend="fused")


def _prepared_shapes(cfg, sharding):
    from repro.models.vit import init_vit
    shapes = jax.eval_shape(lambda: prepare_params(
        init_vit(jax.random.PRNGKey(0), cfg, 10), bits=8))
    return jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sharding), shapes)


def test_photonic_matmul_prequant_compiles(one_chip):
    from repro.kernels.ops import photonic_matmul_prequant
    d = 192                                           # Tiny width
    got = _kernels(
        lambda x, w, s: photonic_matmul_prequant(x, w, s, interpret=False),
        _sds((B, N, d), jnp.float32, one_chip),
        _sds((d, 3 * d), jnp.int8, one_chip),
        _sds((3 * d,), jnp.float32, one_chip))
    assert got == {"photonic_matmul": 1}, got


@pytest.mark.parametrize("mode", ["kv_len", "key_mask"])
def test_flash_attention_masked_compiles(one_chip, mode):
    from repro.kernels.flash_attention import flash_attention_masked
    heads, dh = 3, 64                                 # Tiny: 192 / 3 heads
    q = _sds((B, heads, N, dh), jnp.float32, one_chip)
    if mode == "kv_len":
        got = _kernels(lambda q, k, v: flash_attention_masked(
            q, k, v, kv_len=99, interpret=False), q, q, q)
    else:
        got = _kernels(lambda q, k, v, m: flash_attention_masked(
            q, k, v, m, interpret=False), q, q, q,
            _sds((B, N), jnp.float32, one_chip))
    assert got == {"flash_attention_masked": 1}, got


def test_fused_ffn_int8_compiles(one_chip):
    from repro.kernels.fused_ffn import fused_ffn_int8
    d, dff = 192, 768                                 # Tiny widths
    got = _kernels(
        lambda x, w1, s1, b1, w2, s2, b2: fused_ffn_int8(
            x, w1, s1, b1, w2, s2, b2, interpret=False),
        _sds((B, N, d), jnp.float32, one_chip),
        _sds((d, dff), jnp.int8, one_chip),
        _sds((dff,), jnp.float32, one_chip),
        _sds((dff,), jnp.float32, one_chip),
        _sds((dff, d), jnp.int8, one_chip),
        _sds((d,), jnp.float32, one_chip),
        _sds((d,), jnp.float32, one_chip))
    assert got == {"fused_ffn": 1}, got


def test_fused_encoder_compiles(one_chip):
    """The whole single-jit encoder at Tiny-224: QKV, wo and head on the
    int8 matmul kernel, the RoI flash kernel, the fused FFN — one scan
    body, so each kernel appears once however deep the model."""
    from repro.models.vit import encode_tokens
    cfg = _serving_cfg("tiny")
    pol = ExecPolicy.from_cfg(cfg, training=False)
    pol.interpret = False
    params = _prepared_shapes(cfg, one_chip)
    got = _kernels(lambda p, t: encode_tokens(p, t, cfg, pol), params,
                   _sds((B, N - 1, cfg.d_model), jnp.float32, one_chip))
    assert got == {"photonic_matmul": 5, "flash_attention_masked": 1,
                   "fused_ffn": 1}, got


@pytest.fixture(scope="module", params=["2x2", "4"])
def serving_mesh(request, topo):
    """The two serving meshes of a four-chip host: 2x2 ("data", "model")
    (``model_shards=2``) and data-only ("data",) (the server's default)."""
    shape, axes = (((2, 2), ("data", "model")) if request.param == "2x2"
                   else ((4,), ("data",)))
    return Mesh(np.array(topo.devices).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))


def test_sharded_encoder_compiles(serving_mesh):
    """The shard_map encode on a mesh of the described chips, at Small-224
    (the narrowest published width whose heads split over 2): the only
    form in which the encoder's kernels run on several chips. Its FFN runs
    the compiled dequant epilogue."""
    from repro.distributed.sharding import ShardingCtx, rules_for_mesh
    from repro.models.sharded_encoder import sharded_encode
    mesh = serving_mesh
    ctx = ShardingCtx(mesh, rules_for_mesh(mesh))
    cfg = _serving_cfg("small")
    pol = ExecPolicy.from_cfg(cfg, training=False)
    pol.interpret = False
    params = _prepared_shapes(cfg, NamedSharding(mesh, P()))
    tokens = _sds((B, N - 1, cfg.d_model), jnp.float32,
                  NamedSharding(mesh, P("data")))
    compiled = jax.jit(lambda p, t: sharded_encode(
        p, t, cfg, pol, None, None, ctx)).lower(params, tokens).compile()
    txt = compiled.as_text()
    got = tpu_kernel_counts(txt)
    assert got == {"photonic_matmul": 5, "flash_attention_masked": 1,
                   "dequant_epilogue": 2}, got
    assert "all-reduce" in txt                       # the global absmax
    assert ("all-gather" in txt) == ("model" in mesh.axis_names)


def test_gate_compiles_whole_on_each_device(serving_mesh):
    """The server's embed and MGNet gate on a mesh: each device runs the
    one-device program under shard_map (a TPU cannot partition their
    int8 matmul kernels)."""
    from repro.core.mgnet import MGNetConfig, mgnet_scores
    from repro.models.vit import embed_patches
    from repro.serving.server import _whole_on_each_device
    mesh = serving_mesh
    cfg = _serving_cfg("tiny")
    pol = ExecPolicy.from_cfg(cfg, training=False)
    pol.interpret = False
    mcfg = MGNetConfig(patch=cfg.patch, img_size=cfg.img_size,
                       embed=cfg.mgnet_embed, heads=cfg.mgnet_heads)
    rep = NamedSharding(mesh, P())
    params = _prepared_shapes(cfg, rep)
    frames = _sds((8, 224, 224, 3), jnp.float32, rep)
    embed = _kernels(_whole_on_each_device(
        lambda p, f: embed_patches(p, f, cfg, pol), mesh), params, frames)
    gate = _kernels(_whole_on_each_device(
        lambda p, f: mgnet_scores(p["mgnet"], f, mcfg, pol), mesh),
        params, frames)
    assert embed == {"photonic_matmul": 1}, embed
    assert gate == {"photonic_matmul": 8}, gate


def test_gate_device_programs_compile_on_each_device(serving_mesh):
    """MGNet's padded input gathered from the chunk on the device and the
    per-frame score selection, whole on each device of a mesh, at 224 px:
    every variant a session meets (no reference yet, a reference with
    fresh rows, a reference alone)."""
    from repro.serving.mask_cache import GateOps
    from repro.serving.server import _whole_on_each_device
    mesh = serving_mesh
    rep = NamedSharding(mesh, P())
    ops = GateOps.build(lambda f: _whole_on_each_device(f, mesh))
    frames = _sds((8, 224, 224, 3), jnp.float32, rep)
    idx = _sds((8,), jnp.int32, rep)
    ref = _sds((N - 1,), jnp.float32, rep)
    fresh = _sds((8, N - 1), jnp.float32, rep)
    sub = ops.inputs.lower(frames, idx).compile()
    assert sub.output_shardings.is_equivalent_to(rep, 4)
    for r, f in ((None, fresh), (ref, fresh), (ref, None)):
        out = ops.scores.lower(r, f, idx).compile()
        assert [s.is_equivalent_to(rep, o) for s, o in
                zip(out.output_shardings, (2, 1))] == [True, True]
