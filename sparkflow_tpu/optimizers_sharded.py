"""ZeRO weight-update sharding: optimizer state, gradients and parameters
split over ``dp``.

On a pure data-parallel mesh the standard step all-reduces full gradients and
then runs the optimizer update redundantly on every replica with the state
fully replicated — HBM and FLOPs that scale with model size but not device
count. "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (Xu et al., arXiv:2004.13336) is the TPU fix this module implements:
reduce-scatter the gradients, apply the optimizer to a 1/N shard of the
params+state, all-gather the result. Same math; ~1/N optimizer-state memory
per device; and the reduce_scatter + all_gather pair moves the same bytes over
ICI as the all-reduce it replaces.

Layout. The zero1 GLOBAL optimizer state is ``inner.init`` applied to a
flattened view of the params where every leaf is reshaped ``[n_shards,
ceil(size/n_shards)]`` (flat, zero-padded). Per-param state leaves therefore
carry that same ``[n_shards, s]`` shape and shard row-wise over ``dp``
(:func:`zero1_state_specs` / :func:`place_zero1_state`); scalar leaves (adam's
count, adagrad_da's step, ...) stay replicated. Inside ``shard_map`` the local
view of a sharded leaf is ``[1, s]`` — exactly what :func:`sharded_update`'s
update consumes. Zero padding is inert: every registry optimizer is
elementwise, so pad lanes never contaminate real ones and are trimmed by the
final all-gather.

Stages beyond 1 (driven by :class:`~sparkflow_tpu.sharding.ShardingConfig`):

- ZeRO-2 (:func:`sharded_apply_update`): same reduce-scatter transport, but
  the updated PARAM shards are what all-gathers back — ``apply_updates`` runs
  on the ``[1, s]`` shards, so the full-size update tree and full-size apply
  temporaries never exist. Same elementwise math as stage 1 (the adds happen
  pre-gather instead of post-gather).
- ZeRO-3 (:func:`shard_zero3_params` / :func:`gather_zero3_params`): the
  params themselves live at rest in the flat ``[n_shards, s]`` layout and are
  all-gathered just-in-time inside the loss. Because ``all_gather``'s
  transpose rule IS ``psum_scatter``, differentiating through the gather
  delivers exactly the reduce-scattered gradient shard — the ZeRO-2 scatter
  fused into the backward, with no full gradient tree at rest.

Checkpoint interop. :func:`gather_zero1_state` / :func:`shard_zero1_state`
convert between the zero1 layout and the standard (param-shaped, replicated)
state ``inner.init(params)`` would build. The trainer checkpoints the STANDARD
form, so checkpoint directories are interchangeable between zero1-on/off runs
and across mesh-shape changes (restore re-pads and re-shards for the dp size
of the restoring mesh).

Caveat: the wrapped update runs shard-LOCALLY, so a chained
``optax.clip_by_global_norm`` inside the wrapped transform would measure only
its shard's norm. The trainer's ``auto`` mode therefore declines to shard when
``clip_norm`` (or ``ema_decay``, whose extraction expects the standard layout)
is configured; elementwise companions (``clip_value``, ``weight_decay``,
schedules, ``grad_accum_steps``) compose exactly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _flat_pad(x: jax.Array, n_shards: int) -> jax.Array:
    """Ravel + zero-pad a leaf so its size divides ``n_shards``."""
    flat = jnp.ravel(x)
    pad = (-flat.size) % n_shards
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def _flat2d(params, n_shards: int):
    """The flattened params view the zero1 state is initialized over:
    every leaf ``[n_shards, ceil(size/n_shards)]``."""
    return jax.tree.map(
        lambda p: _flat_pad(p, n_shards).reshape(n_shards, -1), params)


def sharded_update(inner: optax.GradientTransformation, n_shards: int,
                   axis_name: str = "dp",
                   dcn_axis: Optional[str] = None
                   ) -> optax.GradientTransformation:
    """Wrap ``inner`` with ZeRO-1 flatten→pad→shard-local-update→gather
    semantics.

    - ``init(params)`` runs OUTSIDE ``shard_map`` and builds the global
      zero1 state (per-param leaves ``[n_shards, s]``; see module docstring).
    - ``update(grads, state, params)`` runs INSIDE ``shard_map`` with
      ``axis_name`` bound (size ``n_shards``): per leaf it reduce-scatters
      the device-local gradient over the axis (a SUM — normalize grads
      before calling), slices the matching param shard, applies ``inner``
      to the ``[1, s]`` shard views, and all-gathers the update back to the
      full param shape. With ``dcn_axis`` the scattered shard is additionally
      psummed across slices, so the cross-slice DCN hop carries ``1/n_shards``
      of the gradient bytes (the hierarchical two-stage reduction of
      :func:`~sparkflow_tpu.parallel.collectives.hierarchical_psum_mean`,
      minus its final gather — the update runs sharded instead).
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")

    def init_fn(params):
        return inner.init(_flat2d(params, n_shards))

    def update_fn(grads, state, params=None, *, scale=None):
        if params is None:
            raise ValueError("sharded_update requires params at update time")
        idx = jax.lax.axis_index(axis_name)

        def g_shard(g):
            flat = _flat_pad(g, n_shards)
            sh = jax.lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                                      tiled=True)
            if dcn_axis is not None:
                # 1/n_shards of the bytes on the slow cross-slice hop
                sh = jax.lax.psum(sh, dcn_axis)
            if scale is not None:
                # scaling the summed shard (not each addend) keeps the same
                # rounding as the replicated psum(g) * scale path
                sh = sh * scale
            return sh[None, :]

        def p_shard(p):
            flat = _flat_pad(p, n_shards)
            s = flat.size // n_shards
            return jax.lax.dynamic_slice(flat, (idx * s,), (s,))[None, :]

        gs = jax.tree.map(g_shard, grads)
        ps = jax.tree.map(p_shard, params)
        us, state = inner.update(gs, state, ps)

        def unshard(u, like):
            full = jax.lax.all_gather(u[0], axis_name, axis=0, tiled=True)
            return full[:like.size].reshape(like.shape).astype(like.dtype)

        return jax.tree.map(unshard, us, params), state

    return optax.GradientTransformation(init_fn, update_fn)


def sharded_apply_update(inner: optax.GradientTransformation, n_shards: int,
                         axis_name: str = "dp",
                         dcn_axis: Optional[str] = None
                         ) -> optax.GradientTransformation:
    """ZeRO-2 companion of :func:`sharded_update`: identical state layout
    and gradient transport, but the param APPLY also runs on the shards and
    the updated param shards all-gather back.

    Contract change: ``update(grads, state, params, scale=...)`` returns
    ``(new_params, state)`` — the apply is fused, there is no full-size
    update tree for the caller to apply. The per-element math matches
    stage 1 exactly (``p + u`` happens per shard before the gather instead
    of per element after it); bitwise agreement is up to XLA's collective
    scheduling, which isn't pinned across program variants.
    """
    base = sharded_update(inner, n_shards, axis_name, dcn_axis)
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")

    def update_fn(grads, state, params=None, *, scale=None):
        if params is None:
            raise ValueError(
                "sharded_apply_update requires params at update time")
        idx = jax.lax.axis_index(axis_name)

        def g_shard(g):
            flat = _flat_pad(g, n_shards)
            sh = jax.lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                                      tiled=True)
            if dcn_axis is not None:
                sh = jax.lax.psum(sh, dcn_axis)
            if scale is not None:
                sh = sh * scale
            return sh[None, :]

        def p_shard(p):
            flat = _flat_pad(p, n_shards)
            s = flat.size // n_shards
            return jax.lax.dynamic_slice(flat, (idx * s,), (s,))[None, :]

        gs = jax.tree.map(g_shard, grads)
        ps = jax.tree.map(p_shard, params)
        us, state = inner.update(gs, state, ps)
        new_ps = optax.apply_updates(ps, us)

        def unshard(p2, like):
            full = jax.lax.all_gather(p2[0], axis_name, axis=0, tiled=True)
            return full[:like.size].reshape(like.shape).astype(like.dtype)

        return jax.tree.map(unshard, new_ps, params), state

    return optax.GradientTransformation(base.init, update_fn)


def shard_zero3_params(params, n_shards: int):
    """Params -> the ZeRO-3 at-rest layout: every leaf flat-padded to
    ``[n_shards, ceil(size/n_shards)]`` (the same flattened view the zero
    state is initialized over, so ``sharded_update(...).init`` applied to
    the SHARDED params builds the exact stage-1/2 state layout). Place the
    result with :func:`place_zero1_state`-style ``P(axis)`` rows so each
    device physically holds 1/n."""
    return _flat2d(params, n_shards)


def gather_zero3_params(flat_params, template):
    """ZeRO-3 flat layout -> standard param pytree shaped like ``template``
    (real arrays or ShapeDtypeStructs). Runs OUTSIDE shard_map on global
    arrays — the checkpoint / ``trainer.params`` direction."""
    return jax.tree.map(
        lambda f, t: jnp.ravel(jnp.asarray(f))[:t.size].reshape(
            t.shape).astype(t.dtype),
        flat_params, template)


def zero3_param_specs(flat_params, n_shards: int, axis_name: str = "dp"):
    """PartitionSpec pytree for ZeRO-3 at-rest params (row-sharded like the
    state; same rule as :func:`zero1_state_specs`)."""
    return zero1_state_specs(flat_params, n_shards, axis_name)


def zero3_param_shardings(flat_params, mesh: Mesh, n_shards: int,
                          axis_name: str = "dp"):
    """NamedSharding pytree for ZeRO-3 at-rest params — what the trainer
    pins the epoch program's param in/out shardings to."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        zero3_param_specs(flat_params, n_shards, axis_name))


def gathered_param_view(p_local, like, axis_name: str = "dp"):
    """Inside shard_map: reconstruct the full param from this device's
    ``[1, s]`` shard. Linear in the shard, and ``all_gather``'s transpose is
    ``psum_scatter`` — so a loss that consumes this view yields gradients
    that arrive already reduce-scattered (the ZeRO-3 backward fusion)."""
    full = jax.lax.all_gather(p_local[0], axis_name, axis=0, tiled=True)
    return full[:like.size].reshape(like.shape).astype(like.dtype)


def zero1_state_specs(state, n_shards: int, axis_name: str = "dp"):
    """PartitionSpec pytree for a zero1 state: ``[n_shards, ...]`` leaves
    shard row-wise over ``axis_name``, everything else replicates. Works on
    arrays, tracers, or ShapeDtypeStructs. The per-leaf rule is
    :func:`~sparkflow_tpu.sharding.at_rest_leaf_spec` (``layout='flat'``) —
    the same decision ``fsdp_pspecs`` applies to model-shape tensors,
    expressed on the flat ``[n_shards, s]`` layout."""
    from .sharding import at_rest_leaf_spec

    def spec(x):
        shape = getattr(x, "shape", ())
        return at_rest_leaf_spec(shape, axis_name, layout="flat",
                                 n_shards=n_shards)

    return jax.tree.map(spec, state)


def zero1_state_shardings(state, mesh: Mesh, n_shards: int,
                          axis_name: str = "dp"):
    """NamedSharding pytree for a zero1 state — what the trainer pins the
    epoch program's opt-state in/out shardings to (core._jit_epoch_like's
    ``opt_shardings``), keeping the 1/n placement across donated steps."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        zero1_state_specs(state, n_shards, axis_name))


def place_zero1_state(state, mesh: Mesh, n_shards: int,
                      axis_name: str = "dp"):
    """Device-put a zero1 state with its row shardings so each device
    actually holds ~1/n_shards of the per-param leaves."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        state, zero1_state_specs(state, n_shards, axis_name))


def _paired_leaves(inner, params, state):
    """(template_leaf, state_leaf) pairs between ``inner.init(params)``'s
    standard structure and an actual state with the same treedef."""
    std = jax.eval_shape(inner.init, params)
    std_leaves, treedef = jax.tree.flatten(std)
    state_leaves = treedef.flatten_up_to(state)
    return std_leaves, state_leaves, treedef


def gather_zero1_state(inner: optax.GradientTransformation, params, state,
                       n_shards: int):
    """zero1-layout state -> the standard (param-shaped) state
    ``inner.init(params)`` would build — what the trainer checkpoints.

    ``params`` may be a real pytree or ShapeDtypeStructs. Leaves whose shape
    already matches the standard template are copied as-is (scalars, counts;
    also params that happen to BE ``[n_shards, s]``-shaped, where flat2d is
    the identity); mismatched leaves are flat-padded views and trim/reshape
    back.
    """
    std_leaves, z_leaves, treedef = _paired_leaves(inner, params, state)
    out = []
    for tmpl, z in zip(std_leaves, z_leaves):
        z = jnp.asarray(z)
        if tuple(z.shape) == tuple(tmpl.shape):
            out.append(z)
        else:
            out.append(jnp.ravel(z)[:tmpl.size].reshape(tmpl.shape))
    return jax.tree.unflatten(treedef, out)


def shard_zero1_state(inner: optax.GradientTransformation, params, state,
                      n_shards: int):
    """Standard (param-shaped) state -> the zero1 layout for ``n_shards``
    shards: the restore-side inverse of :func:`gather_zero1_state`. Because
    the pad width is recomputed here, a checkpoint written under one dp size
    re-shards correctly onto a mesh with a different one."""
    std_leaves, s_leaves, treedef = _paired_leaves(inner, params, state)
    z_tmpl = jax.eval_shape(lambda p: inner.init(_flat2d(p, n_shards)), params)
    z_leaves = jax.tree.leaves(z_tmpl)
    out = []
    for tmpl, zt, s in zip(std_leaves, z_leaves, s_leaves):
        s = jnp.asarray(s)
        if tuple(zt.shape) == tuple(s.shape):
            out.append(s)
        else:
            out.append(_flat_pad(s, n_shards).reshape(zt.shape))
    return jax.tree.unflatten(treedef, out)


def has_per_param_state(optimizer: optax.GradientTransformation,
                        params) -> bool:
    """True when ``optimizer.init(params)`` carries array (per-param) state —
    the states zero1 sharding actually shrinks. sgd/proximal_gd carry none,
    so ``auto`` mode leaves them replicated (nothing to save)."""
    tmpl = jax.eval_shape(optimizer.init, params)
    return any(getattr(l, "ndim", 0) >= 1 for l in jax.tree.leaves(tmpl))


def state_bytes_per_device(state) -> int:
    """Per-device bytes of a (possibly sharded) state tree, as
    ``tests/test_zero1.py`` reads them: each leaf contributes its local
    shard size, so a replicated tree counts full and a zero1-placed tree
    counts ~1/dp."""
    total = 0
    for leaf in jax.tree.leaves(state):
        if hasattr(leaf, "sharding") and hasattr(leaf.sharding, "shard_shape"):
            shape = leaf.sharding.shard_shape(leaf.shape)
        else:
            shape = getattr(leaf, "shape", ())
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(leaf.dtype).itemsize
    return total


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(getattr(l, "shape", ()), dtype=np.int64))
               * np.dtype(l.dtype).itemsize for l in jax.tree.leaves(tree))


def _row_shard_bytes(tree, n_shards: int) -> int:
    """Per-device bytes of a zero-layout tree: ``[n_shards, s]`` leaves
    contribute one row, everything else (scalars, counts) contributes full."""
    total = 0
    for l in jax.tree.leaves(tree):
        shape = tuple(getattr(l, "shape", ()))
        if len(shape) >= 2 and shape[0] == n_shards:
            shape = (1,) + shape[1:]
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(l.dtype).itemsize
    return total


def zero_memory_report(inner: optax.GradientTransformation, params,
                       n_shards: int, zero_stage: int) -> dict:
    """Structural (eval_shape-exact) per-device byte accounting for one zero
    stage (``tests/test_zero_sharding.py`` holds it to the ideal), valid on
    any backend because it measures layouts, not allocator watermarks.

    - ``params_at_rest`` — param bytes resident per device between steps.
    - ``grads_at_update`` — gradient representation entering the optimizer
      update (full tree at stage 0; the post-scatter ``[1, s]`` shards at
      stages 1-3).
    - ``opt_state_at_rest`` — optimizer state per device (per-param leaves
      row-sharded at stages >= 1; scalar counts replicate).
    - ``apply_temps`` — the transient the apply step materializes: the
      all-gathered full update tree at stages 0-1, shard-sized at 2-3.
    - ``ideal_grad_opt`` — the 1/n_shards share of (full grads + full opt
      state): what ``tests/test_zero_sharding.py`` holds stage 2 within 1.3x of
      (padding and replicated scalars are why measured > ideal).
    """
    if zero_stage not in (0, 1, 2, 3):
        raise ValueError(f"zero_stage must be 0..3, got {zero_stage!r}")
    params_b = _tree_bytes(params)
    opt_std = jax.eval_shape(inner.init, params)
    opt_std_b = _tree_bytes(opt_std)
    if zero_stage == 0:
        report = dict(params_at_rest=params_b, grads_at_update=params_b,
                      opt_state_at_rest=opt_std_b, apply_temps=params_b)
    else:
        flat = jax.eval_shape(lambda p: _flat2d(p, n_shards), params)
        opt_z = jax.eval_shape(lambda p: inner.init(_flat2d(p, n_shards)),
                               params)
        shard_b = _row_shard_bytes(flat, n_shards)
        report = dict(
            params_at_rest=(shard_b if zero_stage >= 3 else params_b),
            grads_at_update=shard_b,
            opt_state_at_rest=_row_shard_bytes(opt_z, n_shards),
            apply_temps=(params_b if zero_stage == 1 else shard_b))
    report["grad_opt_at_update"] = (report["grads_at_update"]
                                    + report["opt_state_at_rest"])
    report["ideal_grad_opt"] = (params_b + opt_std_b) / max(n_shards, 1)
    report["full_params"] = params_b
    report["full_opt_state"] = opt_std_b
    report["n_shards"] = n_shards
    report["zero_stage"] = zero_stage
    return report
