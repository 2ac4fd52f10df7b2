"""Data-parallel train steps via shard_map: one builder, four zero stages.

``core.make_train_step``'s GSPMD jit now keeps the flash kernel too — its
trace runs under ``ops.attention.sharded_attention``, which nests a
shard_map around just the attention op. This module is the WHOLE-STEP
shard_map form: every operand is the device-LOCAL shard end to end, so all
pallas kernels run per-device with no partitioner involved anywhere — the
standard recipe for custom kernels on a mesh (scaling-book §sharding: map
the kernel, let the collectives handle the rest).

:func:`make_dp_train_step` is the single builder, driven by a declarative
:class:`~sparkflow_tpu.sharding.ShardingConfig` instead of one function per
strategy. The zero stage selects how much of the update shards over the
data axis (Xu et al., arXiv:2004.13336; see ``docs/sharding.md``):

- stage 0 — replicated update: grads ``psum``-reduced, optax runs
  identically on every device (the classic DP step).
- stage 1 — optimizer state sharded: grads reduce-scatter, the update runs
  on each device's 1/dp flattened shard, UPDATES all-gather back.
- stage 2 — + sharded apply: the updated PARAM shards all-gather instead,
  so full-size update temporaries never exist.
- stage 3 — + params sharded at rest in the flat ``[dp, s]`` layout,
  all-gathered just-in-time inside the loss; ``all_gather``'s transpose
  rule IS ``psum_scatter``, so the backward delivers gradients already
  reduce-scattered.

Semantics are identical across stages (loss is the global masked mean;
per-element float ops match, with reduction-order-bounded differences
between stage 0's psum and stages 1-3's scatter transport). Dropout rngs
fold in the device index so shards draw independent masks.

``make_dp_shardmap_train_step`` / ``make_dp_zero1_train_step`` remain as
thin shims constructing the equivalent ShardingConfig.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax
from ..sharding import ShardingConfig, as_sharding_config
from jax.sharding import Mesh, PartitionSpec as P


def _check_dcn_axis(mesh: Mesh, dp_axis: str, dcn_axis: Optional[str]):
    """Validate the (dp, dcn) axis pair against the mesh — delegates to
    :meth:`ShardingConfig.validate` so the builders and the declarative
    config raise the SAME actionable errors (dcn==dp duplicate-axis,
    typo'd axis name)."""
    if dcn_axis is None:
        return
    ShardingConfig(data_axis=dp_axis, dcn_axis=dcn_axis).validate(
        mesh, require_data_axis=False)


def make_dp_train_step(model, optimizer, mesh: Mesh,
                       input_name, label_name: Optional[str],
                       sharding: Any = None,
                       param_template=None,
                       _raw: bool = False):
    """The unified whole-step shard_map train step for zero stages 0-3.

    Signature matches ``core.make_train_step``'s:
    ``step(params, opt_state, x, y, mask, rng) -> (params, opt_state, loss)``
    with x/y/mask sharded over the config's batch axes (row counts must
    divide the axes' product) and params replicated — except at stage 3,
    where ``params`` is the flat ZeRO-3 tree
    (:func:`~sparkflow_tpu.optimizers_sharded.shard_zero3_params`) sharded
    row-wise, and ``param_template`` supplies the standard param
    shapes/dtypes (defaults to ``eval_shape`` of ``model.init``).

    For stages >= 1, ``optimizer`` is the plain (unwrapped) transformation;
    callers build the matching sharded state with
    ``sharded_update(optimizer, dp, axis).init(params)`` (stage 3: init over
    the flat params — same layout either way) and place it with
    :func:`~sparkflow_tpu.optimizers_sharded.place_zero1_state`.

    ``sharding.dcn_axis`` names a second, slower batch axis for multi-slice
    meshes (mesh ``{dcn: n_slices, dp: chips_per_slice}``): the batch shards
    over BOTH axes and the gradient merge becomes the hierarchical two-stage
    reduction — reduce_scatter inside each slice over ICI, a 1/n_ici-sized
    all-reduce across slices over DCN. Mathematically equivalent to the flat
    psum (bitwise differences from the changed reduction order stay within
    the pinned parity tolerance); the cross-slice wire traffic drops by the
    ICI axis size.

    ``_raw=True`` returns the un-jitted stepper (shard_map applied, no jit)
    for slotting into the trainer's epoch ``step_fn`` machinery.
    """
    from ..core import make_feeds_builder
    from ..optimizers_sharded import (gathered_param_view, sharded_update,
                                      sharded_apply_update, zero1_state_specs,
                                      zero3_param_specs)
    from .collectives import hierarchical_psum_mean

    cfg = as_sharding_config(sharding)
    cfg.validate(mesh, require_data_axis=True)
    if cfg.data_axis not in mesh.axis_names:
        raise ValueError(
            f"data_axis={cfg.data_axis!r} is not a mesh axis "
            f"{list(mesh.axis_names)}")
    stage = cfg.zero_stage
    dp_axis, dcn_axis = cfg.data_axis, cfg.dcn_axis
    build_feeds = make_feeds_builder(input_name, label_name)
    n_shards = mesh.shape[dp_axis]
    two_level = dcn_axis is not None
    axes = (dcn_axis, dp_axis) if two_level else (dp_axis,)
    data_spec = cfg.data_spec(mesh)

    def prologue(rng):
        r = rng
        for a in axes:
            r = jax.random.fold_in(r, jax.lax.axis_index(a))
        return r

    def loss_parts(params, x, y, mask, rng):
        def local_sum(p):
            lv = model.loss_vector(p, build_feeds(x, y), train=True, rng=rng)
            return jnp.sum(lv * mask)

        s, grads = jax.value_and_grad(local_sum)(params)
        n = jnp.maximum(jax.lax.psum(jnp.sum(mask), axes), 1.0)
        loss = jax.lax.psum(s, axes) / n
        return grads, n, loss

    if stage == 0:
        def step(params, opt_state, x, y, mask, rng):
            rng = prologue(rng)
            grads, n, loss = loss_parts(params, x, y, mask, rng)
            if two_level:
                # sum-reduce hierarchically, then rescale mean-by-count: the
                # helper divides by the device count, the loss divides by
                # the (psummable) example count
                total = jax.lax.psum(1, axes)
                grads = jax.tree.map(
                    lambda g: g * (total / n),
                    hierarchical_psum_mean(grads, ici_axis=dp_axis,
                                           dcn_axis=dcn_axis))
            else:
                grads = jax.tree.map(
                    lambda g: jax.lax.psum(g, dp_axis) / n, grads)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        param_spec = P()
        opt_spec_of = lambda opt_state: P()

    elif stage in (1, 2):
        wrapped = (sharded_update if stage == 1 else sharded_apply_update)(
            optimizer, n_shards, dp_axis, dcn_axis)

        def step(params, opt_state, x, y, mask, rng):
            rng = prologue(rng)
            grads, n, loss = loss_parts(params, x, y, mask, rng)
            # the 1/n mean-normalization applies AFTER the scatter-sum
            # (inside the wrapped update), matching the replicated step's
            # psum(g) / n rounding instead of summing pre-scaled addends
            if stage == 1:
                updates, opt_state = wrapped.update(grads, opt_state, params,
                                                    scale=1.0 / n)
                params = optax.apply_updates(params, updates)
            else:
                params, opt_state = wrapped.update(grads, opt_state, params,
                                                   scale=1.0 / n)
            return params, opt_state, loss

        param_spec = P()
        opt_spec_of = lambda opt_state: zero1_state_specs(
            opt_state, n_shards, dp_axis)

    else:  # stage 3: params sharded at rest, gathered just-in-time
        if param_template is None:
            param_template = jax.eval_shape(model.init,
                                            jax.random.PRNGKey(0))
        tmpl = jax.tree.map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), param_template)

        def step(p_flat, opt_state, x, y, mask, rng):
            rng = prologue(rng)

            def local_sum(pf):
                # the gather is the forward; its transpose (psum_scatter
                # over dp) is the backward — grads come back as [1, s]
                # shards already summed across the dp axis
                full = jax.tree.map(
                    lambda p, t: gathered_param_view(p, t, dp_axis),
                    pf, tmpl)
                lv = model.loss_vector(full, build_feeds(x, y), train=True,
                                       rng=rng)
                return jnp.sum(lv * mask)

            s, g_sh = jax.value_and_grad(local_sum)(p_flat)
            n = jnp.maximum(jax.lax.psum(jnp.sum(mask), axes), 1.0)
            loss = jax.lax.psum(s, axes) / n

            def norm(g):
                if dcn_axis is not None:
                    # only the 1/dp shard crosses the slow DCN hop
                    g = jax.lax.psum(g, dcn_axis)
                return g * (1.0 / n)

            g_sh = jax.tree.map(norm, g_sh)
            us, opt_state = optimizer.update(g_sh, opt_state, p_flat)
            p_flat = optax.apply_updates(p_flat, us)
            return p_flat, opt_state, loss

        param_spec = None  # derived per call from the flat tree
        opt_spec_of = lambda opt_state: zero1_state_specs(
            opt_state, n_shards, dp_axis)

    def stepper(params, opt_state, x, y, mask, rng):
        # the opt-state (and stage-3 param) spec trees depend on structure
        # only known at call time — built per call (cheap; under jit this
        # traces once per structure anyway)
        o_spec = opt_spec_of(opt_state)
        p_spec = (zero3_param_specs(params, n_shards, dp_axis)
                  if stage >= 3 else param_spec)
        sm = jax.shard_map(
            step, mesh=mesh,
            in_specs=(p_spec, o_spec, data_spec, data_spec, data_spec, P()),
            out_specs=(p_spec, o_spec, P()),
            check_vma=False)
        return sm(params, opt_state, x, y, mask, rng)

    if _raw:
        return stepper
    return jax.jit(stepper, donate_argnums=(0, 1))


def make_dp_shardmap_train_step(model, optimizer, mesh: Mesh,
                                input_name, label_name: Optional[str],
                                dp_axis: str = "dp",
                                dcn_axis: Optional[str] = None):
    """Stage-0 shim over :func:`make_dp_train_step`: the replicated-update
    whole-step shard_map form (grads psum-merged, optax runs identically on
    every device)."""
    cfg = ShardingConfig(data_axis=dp_axis, dcn_axis=dcn_axis, zero_stage=0)
    return make_dp_train_step(model, optimizer, mesh, input_name, label_name,
                              sharding=cfg)


def make_dp_zero1_train_step(model, optimizer, mesh: Mesh,
                             input_name, label_name: Optional[str],
                             dp_axis: str = "dp",
                             dcn_axis: Optional[str] = None,
                             _raw: bool = False):
    """Stage-1 shim over :func:`make_dp_train_step`: gradients
    reduce-scatter over ``dp_axis``, the optimizer update runs on each
    device's 1/dp shard with the state sharded the same way, and the
    updates all-gather back (Xu et al., arXiv:2004.13336)."""
    cfg = ShardingConfig(data_axis=dp_axis, dcn_axis=dcn_axis, zero_stage=1)
    return make_dp_train_step(model, optimizer, mesh, input_name, label_name,
                              sharding=cfg, _raw=_raw)
