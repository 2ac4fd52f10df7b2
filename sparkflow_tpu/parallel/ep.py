"""Expert parallelism via shard_map + all_to_all (the communicating form).

Two EP implementations coexist:

1. GSPMD (default): expert banks carry ``P('ep', ...)`` PartitionSpecs and a
   plain jit partitions the capacity-dispatch einsums (``models/moe.py``).
2. This module: the model runs under ``shard_map`` with the batch AND the
   expert bank sharded over ONE axis — every device holds a batch shard plus
   ``E/n`` experts, and MoE layers exchange tokens with ``lax.all_to_all``
   over ICI (``ops/moe_dispatch.all_to_all_moe_ffn``), the GShard pipeline.

Gradient plumbing falls out of the layout: expert-bank gradients are already
complete on the owning device (it computed its experts over every token that
routed there — no collective needed); all other parameters are replicated, so
their gradients ``psum``. The optimizer update runs OUTSIDE shard_map under
GSPMD with the same placement, so optimizer state shards exactly like params.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from .tp import filter_pspec, shard_params


def _has_axis(spec: P, axis: str) -> bool:
    return any(a == axis or (isinstance(a, (list, tuple)) and axis in a)
               for a in spec)


def make_moe_shardmap_train_step(model, optimizer, mesh: Mesh,
                                 ep_axis: str = "ep"):
    """Train step for an ``ep_axis``-enabled MoE LM (see
    ``transformer_moe_lm``'s ``ep_axis`` config).

    Signature: ``step(params, opt_state, ids, mask, rng) ->
    (params, opt_state, loss)`` — ids/mask row counts must divide the axis;
    params placed per ``shard_params(model.param_pspecs())`` (expert leaves
    sharded over ``ep_axis``, everything else replicated).
    """
    if getattr(model, "ep_axis", None) != ep_axis:
        raise ValueError(
            f"model.ep_axis={getattr(model, 'ep_axis', None)!r}; build the "
            f"model with ep_axis={ep_axis!r} so its MoE layers dispatch via "
            f"all_to_all inside shard_map")
    pspecs = jax.tree.map(lambda s: filter_pspec(s, mesh),
                          model.param_pspecs(),
                          is_leaf=lambda x: isinstance(x, P))
    data_spec = P(ep_axis)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(pspecs, data_spec, data_spec, P()),
             out_specs=(pspecs, P()),
             check_vma=False)
    def grad_fn(params, ids, mask, rng):
        rng = jax.random.fold_in(rng, jax.lax.axis_index(ep_axis))

        def local_sum(p):
            lv = model.loss_vector(
                p, {"input_ids": ids, "attention_mask": mask}, train=True,
                rng=rng)
            return jnp.sum(lv)

        s, grads = jax.value_and_grad(local_sum)(params)
        n_glob = jnp.maximum(
            jax.lax.psum(jnp.asarray(ids.shape[0], jnp.float32), ep_axis), 1.0)
        loss = jax.lax.psum(s, ep_axis) / n_glob

        def reduce_grad(g, spec):
            # spec is a static PartitionSpec, not data: resolves at trace time
            if _has_axis(spec, ep_axis):  # graftcheck: disable=GC-A202
                return g / n_glob          # expert slice: already complete
            return jax.lax.psum(g, ep_axis) / n_glob

        grads = jax.tree.map(reduce_grad, grads, pspecs,
                             is_leaf=lambda x: isinstance(x, P) or not
                             isinstance(x, dict))
        return grads, loss

    def step(params, opt_state, ids, mask, rng):
        grads, loss = grad_fn(params, ids, mask, rng)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))


def place_moe_params(model, params, mesh: Mesh):
    """Convenience: shard the expert bank over the mesh per param_pspecs."""
    return shard_params(params, mesh, model.param_pspecs())
