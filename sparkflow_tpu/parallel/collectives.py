"""XLA collectives: the framework's gradient/weight transport layer.

The TPU-native replacement for the reference's HTTP weight/gradient
transport (``GET /parameters`` / ``POST /update``,
``sparkflow/HogwildSparkModel.py:22-35``): gradient merge is a ``psum``
compiled into the train step, riding ICI/DCN — weights never leave the
device mesh. Besides the named one-liners (kept as the vocabulary the step
builders share), :func:`hierarchical_psum_mean` is the pod-scale form:
a topology-aware two-level reduction whose cross-slice DCN hop carries only
``1/n_ici`` of the gradient bytes (used by
``parallel.dp.make_dp_shardmap_train_step(dcn_axis=...)``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp



def psum_mean(tree, axis_name: str):
    """All-reduce-mean a pytree over a mesh axis (gradient averaging)."""
    n = jax.lax.psum(1, axis_name)
    return jax.tree.map(lambda x: jax.lax.psum(x, axis_name) / n, tree)


def psum(tree, axis_name: str):
    return jax.tree.map(lambda x: jax.lax.psum(x, axis_name), tree)


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0):
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def ppermute_ring(x, axis_name: str, shift: int = 1):
    """Rotate shards around the mesh-axis ring (building block of ring
    attention and pipeline schedules)."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def hierarchical_psum_mean(tree, ici_axis: str, dcn_axis: str):
    """Two-level gradient mean for multi-slice meshes (mesh axes ordered
    [dcn, ici]): per leaf, ``psum_scatter`` over the fast intra-slice ICI
    axis, all-reduce the 1/n_ici-sized shard over the slow cross-slice DCN
    axis, then ``all_gather`` back over ICI.

    Mathematically equivalent to a flat ``psum`` over both axes divided by
    the total device count; bitwise differences are possible because the
    reduction order changes, and stay bounded by the pinned tolerance in the
    parity tests. The point is the WIRE layout: the DCN hop (tens of
    GB/s across slices, vs ~100s of GB/s ICI within one) carries only
    ``1/n_ici`` of the gradient bytes, instead of the full tree a flat
    cross-axis psum would move. This is the standard pod-scale data-parallel
    reduction (scaling-book §sharding: reduce_scatter -> cross-slice
    all-reduce -> all_gather).

    Must run inside ``shard_map`` with both axes bound. Leaves whose size
    does not divide ``n_ici`` are flat-padded for the scatter and unpadded
    after the gather (exactness unaffected: padding reduces to zeros).
    """
    n_ici = jax.lax.axis_size(ici_axis)
    total = n_ici * jax.lax.axis_size(dcn_axis)

    def leaf(x):
        flat = jnp.ravel(x)
        pad = (-flat.size) % n_ici
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        shard = jax.lax.psum_scatter(flat, ici_axis, scatter_dimension=0,
                                     tiled=True)
        shard = jax.lax.psum(shard, dcn_axis)  # 1/n_ici of the bytes on DCN
        out = jax.lax.all_gather(shard, ici_axis, axis=0, tiled=True)
        if pad:
            out = out[:x.size]
        return (out / total).reshape(x.shape).astype(x.dtype)

    return jax.tree.map(leaf, tree)
