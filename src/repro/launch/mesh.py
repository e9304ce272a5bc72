"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — critical because the dry-run
overrides the host device count via XLA_FLAGS *before* first jax init,
while tests/benches must keep seeing the single real CPU device.

Meshes (pinned by the assignment):
  single-pod : (16, 16)            axes ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16)         axes ("pod", "data", "model") = 512 chips
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "make_serving_mesh",
           "batch_shard_count"]


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: GSPMD propagates the
    shardings, and gathers and slices of sharded arrays stay legal (the
    ``Explicit`` default would raise ``ShardingTypeError`` on them)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the real host devices (examples / integration tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return _auto_mesh((data, model), ("data", "model"))


def make_serving_mesh(model: int = 1):
    """Serving mesh over every visible device, or None on a single device.

    ``model == 1`` (default): 1-D ("data",) mesh — the encode batch axis
    data-parallelizes (distributed.sharding.DATA_RULES), params replicate,
    each device encodes a slice of the micro-batch. None keeps the
    single-device path annotation-free (ShardingCtx is never installed).

    ``model > 1``: 2-D ("data", "model") mesh of shape (n // model,
    model) — attention heads and the FFN hidden dim shard over "model"
    (distributed.sharding.MODEL_RULES) so big ViT variants serve at all,
    batch still splits over "data". Raises when the device count cannot
    host the requested model axis (silent clamping would change which
    kernels run)."""
    n = len(jax.devices())
    if model > 1:
        if model > n:
            raise ValueError(f"model={model} shards need at least {model} "
                             f"devices, have {n}")
        if n % model != 0:
            raise ValueError(f"device count {n} is not divisible by "
                             f"model={model}")
        return _auto_mesh((n // model, model), ("data", "model"))
    if n < 2:
        return None
    return _auto_mesh((n,), ("data",))


def batch_shard_count(mesh) -> int:
    """Device count along the batch (DP) axes = pod x data."""
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n
