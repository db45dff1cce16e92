"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS before first jax init and then calls this.
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, devices=None):
    """`jax.make_mesh` with every axis `Auto`: shardings propagate through
    jit as GSPMD decides (jax's own default is `Explicit`)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=auto, devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh.

    single pod:  (16, 16)    axes (data, model)   = 256 v5e chips
    multi pod :  (2, 16, 16) axes (pod, data, model) = 512 chips, `pod` crosses DCN
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist locally (tests / examples)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data * model} devices, have {n}")
    return make_mesh((data, model), ("data", "model"))


OUTER_AXES = ("pod",)   # mesh axes that cross DCN (inter-pod network)


def tier_axes(mesh) -> tuple:
    """Factor `mesh.axis_names` into the (outer, inner) wire tiers.

    Outer axes cross the slow inter-pod network (DCN); inner axes are the
    fast intra-pod interconnect (ICI). Hierarchical strategies rely on the
    linear device index over all axes decomposing as
    `outer_index * inner_shards + inner_index`, which holds iff the outer
    axes are a LEADING prefix of the mesh — enforced here.
    """
    names = tuple(mesh.axis_names)
    outer = tuple(a for a in names if a in OUTER_AXES)
    inner = tuple(a for a in names if a not in OUTER_AXES)
    if outer and names[:len(outer)] != outer:
        raise ValueError(
            f"outer (DCN) axes {outer} must lead the mesh, got {names}; "
            "construct meshes (pod, ...) first, as make_production_mesh "
            "does")
    return outer, inner


def tier_shards(mesh) -> tuple:
    """(outer_shards, inner_shards) device counts for the two tiers."""
    outer, inner = tier_axes(mesh)
    po = 1
    for a in outer:
        po *= int(mesh.shape[a])
    pi = 1
    for a in inner:
        pi *= int(mesh.shape[a])
    return po, pi


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch is sharded over (DP axes present in mesh)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
