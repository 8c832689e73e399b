"""Device mesh construction and sharding-constraint helpers.

Axis names (the TPU counterpart of the reference's pp×dp×tp rank grid,
dist_utils.py:149-263):

- ``dp``: data/attention-parallel replicas (reference DP attention)
- ``tp``: tensor parallel (Megatron column/row splits → mesh-axis shardings)
- ``ep`` is not a separate axis: experts shard over dp×tp flattened, exactly
  like the reference's EP = dp*tp (dist_utils.py:81-86).
- ``pp`` stages are separate jit programs per host group (not a GSPMD axis);
  see gllm_tpu/parallel/pipeline.py.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DP = "dp"
AXIS_TP = "tp"
AXIS_SP = "sp"


def make_mesh(dp: int = 1, tp: int = 1, sp: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    # axis order (dp, sp, tp): tp innermost so its all-reduces ride
    # adjacent chips; the sp ring's neighbor exchanges stay within the
    # next-contiguous block
    if devices is None:
        devices = jax.devices()
    n = dp * tp * sp
    if len(devices) < n:
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(dp, sp, tp)
    return Mesh(arr, (AXIS_DP, AXIS_SP, AXIS_TP))


def active_mesh():
    """The abstract mesh bound by the innermost ``mesh_context`` (an
    empty mesh — falsy ``shape_tuple`` — when none is)."""
    return jax.sharding.get_abstract_mesh()


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh]):
    if mesh is None:
        yield
        return
    with jax.sharding.set_mesh(mesh):
        yield


def shard_hint(x, *spec):
    """with_sharding_constraint that degrades gracefully:

    - no active mesh (single-chip): no-op, same traced code everywhere
    - axis name absent from the mesh: that dim becomes unsharded
    - dim not divisible by the axis size: unsharded (matches the
      divisibility gating in parallel/shardings.py — e.g. 4 kv heads on
      tp=8 stay replicated instead of forcing reshard collectives)
    """
    mesh = active_mesh()
    if not mesh.shape_tuple:
        return x
    sizes = dict(mesh.shape_tuple)

    def axis_ok(name, dim):
        size = sizes.get(name)
        return size is not None and x.shape[dim] % size == 0

    cleaned = []
    for dim, s in enumerate(spec):
        if s is None:
            cleaned.append(None)
        elif isinstance(s, str):
            cleaned.append(s if axis_ok(s, dim) else None)
        else:  # tuple of axes
            cleaned.append(s if all(axis_ok(a, dim) for a in s) else None)
    return jax.lax.with_sharding_constraint(x, P(*cleaned))


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
