"""Sharding rules + context tests (single-device degenerate mesh; the
512-device production meshes are exercised by launch/dryrun.py only)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.core import quant
from repro.core.backend import QuantizedWeight, place_params
from repro.distributed.collectives import (exact_int_psum,
                                           replicated_absmax_scale)
from repro.distributed.sharding import (DATA_RULES, DEFAULT_RULES,
                                        MODEL_RULES, MULTIPOD_RULES,
                                        ShardingCtx, current_ctx,
                                        logical_spec, named_sharding,
                                        rules_for_mesh, shard, use_sharding,
                                        validate_rules)


def _mesh(shape, axes):
    """A mesh of the kind launch/mesh.py builds: every axis Auto."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


@pytest.fixture(scope="module")
def ctx():
    mesh = _mesh((1, 1), ("data", "model"))
    return ShardingCtx(mesh, DEFAULT_RULES)


def test_shard_noop_without_ctx():
    x = jnp.ones((4, 8))
    y = shard(x, "batch", "embed")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert current_ctx() is None


def test_ctx_installs_and_restores():
    mesh = _mesh((1, 1), ("data", "model"))
    assert current_ctx() is None
    with use_sharding(mesh):
        assert current_ctx() is not None
        with use_sharding(None):
            assert current_ctx() is None
        assert current_ctx() is not None
    assert current_ctx() is None


def test_spec_mapping(ctx):
    assert ctx.spec("batch", "seq", "embed") == P("data", None, None)
    assert ctx.spec("batch", None, "mlp") == P("data", None, "model")
    assert ctx.spec("p_embed", "p_mlp") == P("data", "model")


def test_multipod_rules_add_pod_axis():
    assert MULTIPOD_RULES["batch"] == ("pod", "data")
    assert MULTIPOD_RULES["p_embed"] == ("pod", "data")
    assert MULTIPOD_RULES["p_mlp"] == "model"       # TP unchanged


def test_logical_spec_divisibility_fallback():
    """Rules whose axis size does not divide the dim drop to replicated —
    e.g. GQA kv_heads=8 on model=16, odd vocabs, batch=1 decode."""

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    fctx = ShardingCtx(FakeMesh(), DEFAULT_RULES)
    # 50280 % 16 != 0 -> vocab dim replicated
    spec = logical_spec((32, 50280), ("batch", "vocab"), fctx)
    assert spec == P("data", None)
    # batch=1 under data=16 -> replicated
    spec = logical_spec((1, 128), ("batch", "seq"), fctx)
    assert spec == P(None, None)
    # clean divisible case keeps both
    spec = logical_spec((32, 4096), ("batch", "mlp"), fctx)
    assert spec == P("data", "model")


def test_shard_applies_constraint_under_jit(ctx):
    with use_sharding(ctx.mesh, ctx.rules):
        @jax.jit
        def f(x):
            return shard(x, "batch", "embed") * 2

        y = f(jnp.ones((4, 8)))
    np.testing.assert_array_equal(np.asarray(y), 2.0)


def test_shard_rank_mismatch_raises(ctx):
    with use_sharding(ctx.mesh, ctx.rules):
        with pytest.raises(ValueError, match="rank"):
            shard(jnp.ones((4, 8)), "batch")


def test_named_sharding_roundtrip(ctx):
    ns = named_sharding((8, 16), ("batch", "mlp"), ctx)
    assert ns.spec == P("data", "model")


# ---- 2-D serving mesh: MODEL_RULES / rules_for_mesh / validate_rules ----


def test_model_rules_mapping():
    """MODEL_RULES shards heads/d_ff over "model", keeps embed replicated
    (no FSDP at inference — see the table's comment)."""
    mesh = _mesh((1, 1), ("data", "model"))
    mctx = ShardingCtx(mesh, MODEL_RULES)
    assert mctx.spec("batch", "heads", None) == P("data", "model", None)
    assert mctx.spec("p_embed", "p_heads") == P(None, "model")
    assert mctx.spec("p_embed", "p_mlp") == P(None, "model")
    # embed dims replicate: the prepared int8 cache is small
    assert MODEL_RULES.get("p_embed") is None


def test_rules_for_mesh_selection():
    assert rules_for_mesh(None) is None
    assert rules_for_mesh(_mesh((1,), ("data",))) is DATA_RULES
    assert rules_for_mesh(
        _mesh((1, 1), ("data", "model"))) is MODEL_RULES
    assert rules_for_mesh(
        _mesh((1, 1, 1), ("pod", "data", "model"))) is MULTIPOD_RULES


def test_validate_rules_raises_on_unmapped_axis():
    """A size>1 mesh axis no rule uses would silently replicate everything
    — validate_rules turns that into a loud error. Size-1 axes are exempt."""

    class FakeMesh:
        shape = {"data": 2, "model": 2}
        axis_names = ("data", "model")

    with pytest.raises(ValueError, match="model"):
        validate_rules(FakeMesh(), DATA_RULES)
    validate_rules(FakeMesh(), MODEL_RULES)      # uses both axes: fine

    class DegenerateModel:
        shape = {"data": 2, "model": 1}
        axis_names = ("data", "model")

    validate_rules(DegenerateModel(), DATA_RULES)    # size-1 exempt


def test_use_sharding_validates_explicit_rules():
    class FakeMesh:
        shape = {"data": 2, "model": 2}
        axis_names = ("data", "model")

    with pytest.raises(ValueError, match="model"):
        with use_sharding(FakeMesh(), DATA_RULES):
            pass


def test_place_params_pins_quantized_weights():
    """place_params puts QuantizedWeight codes *and* scales under the
    logical-axis sharding; the scale's size-1 contraction dim falls back
    to replicated so per-out-channel scales follow their columns."""
    mesh = _mesh((1, 1), ("data", "model"))
    mctx = ShardingCtx(mesh, MODEL_RULES)
    params = {
        "wq": QuantizedWeight(jnp.zeros((8, 16), jnp.int8),
                              jnp.zeros((1, 16), jnp.float32), 8),
        "ln": jnp.ones((8,)),
    }
    axes = {"wq": ("p_embed", "p_heads"), "ln": (None,)}
    placed = place_params(params, axes, mctx)
    assert placed["wq"].wq.sharding.spec == P(None, "model")
    assert placed["wq"].scale.sharding.spec == P(None, "model")
    assert placed["ln"].sharding.is_fully_replicated
    assert placed["wq"].bits == 8


# ---- exact collectives (distributed/collectives.py) ----


def test_replicated_absmax_scale_bitwise_matches_unsharded():
    """Inside shard_map on a degenerate mesh the pmax is an identity, so
    the result must equal core.quant.absmax_scale bit for bit — the op
    order (max -> pmax -> eps clamp -> reciprocal-multiply) is the whole
    contract."""
    mesh = _mesh((1, 1), ("data", "model"))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32), jnp.float32)
    ref = quant.absmax_scale(x, bits=8)
    got = jax.shard_map(
        lambda t: replicated_absmax_scale(t, 8, ("data", "model")),
        mesh=mesh, in_specs=P(None, None), out_specs=P(),
        check_vma=False)(x)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_exact_int_psum_rejects_float():
    with pytest.raises(TypeError, match="integer"):
        exact_int_psum(jnp.ones((4,), jnp.float32), "model")


def test_exact_int_psum_identity_on_degenerate_axis():
    mesh = _mesh((1, 1), ("data", "model"))
    x = jnp.arange(8, dtype=jnp.int32)
    got = jax.shard_map(lambda t: exact_int_psum(t, "model"), mesh=mesh,
                        in_specs=P(None), out_specs=P(None),
                        check_vma=False)(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
