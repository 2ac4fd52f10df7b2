"""Sequence/context parallelism: training with ring attention over ``sp``.

The long-context path (SURVEY.md §5 lists this as absent in the reference; here
it is first-class): activations shard along the sequence axis across the mesh's
``sp`` ring, attention runs :func:`~sparkflow_tpu.ops.ring_attention` (K/V
rotating over ICI), and the loss/gradients merge with token-weighted psums.
Attention itself is exact (the ring visits every K/V block); the next-token
loss excludes the n_shards-1 shard-boundary targets per example (each shard
predicts only its own tokens 1..S_local-1), so loss/grad differ from unsharded
training by that small, fixed exclusion.

Works for the causal LM family (``transformer_lm``); batch can shard over
``dp`` simultaneously (2-D mesh ``{"dp": a, "sp": b}``).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_sp_train_step(model, optimizer, mesh: Mesh, dp_axis: Optional[str] = "dp",
                       sp_axis: str = "sp", _raw: bool = False):
    """Jitted sequence-parallel LM train step.

    Signature: ``step(params, opt_state, ids, mask, rng) ->
    (params, opt_state, loss)`` with ``ids``/``mask`` shaped [B, S] sharded
    (dp, sp); params/opt_state replicated.

    Loss is the global token-weighted NLL: each shard computes (sum_nll,
    token_count) over its local tokens, both psum over the mesh (boundary
    targets between shards excluded — see module docstring).
    """
    import copy

    # private copy: setting sp_axis on the caller's model would break its
    # later use outside shard_map (ring attention needs a bound axis name)
    model = copy.copy(model)
    model.sp_axis = sp_axis
    axes = tuple(a for a in (dp_axis, sp_axis) if a and a in mesh.axis_names)

    def local_sums(params, ids, mask, rng):
        # next-token NLL over local tokens; boundary tokens between shards are
        # handled by the ring (each shard predicts its own tokens 1..n from
        # its local logits; the first local token of shard i>0 is dropped,
        # matching the per-example shift inside the model's loss)
        feeds = {"input_ids": ids, "attention_mask": mask}
        lv = model.loss_vector(params, feeds, train=True, rng=rng)  # [B_local]
        w = jnp.sum(mask[:, 1:], axis=-1) if mask is not None else (
            jnp.full((ids.shape[0],), ids.shape[1] - 1, jnp.float32))
        return jnp.sum(lv * w), jnp.sum(w)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(), P(), P(dp_axis, sp_axis), P(dp_axis, sp_axis), P()),
             out_specs=(P(), P(), P()),
             check_vma=False)
    def step(params, opt_state, ids, mask, rng):
        # decorrelate dropout across shards
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axes[0]) if axes else 0)
        if sp_axis in mesh.axis_names:
            rng = jax.random.fold_in(rng, jax.lax.axis_index(sp_axis))

        def scalar_loss(p):
            s, c = local_sums(p, ids, mask, rng)
            return s, c

        (snll, cnt), grads = jax.value_and_grad(scalar_loss, has_aux=True)(params)
        total_nll = jax.lax.psum(snll, axes)
        total_cnt = jax.lax.psum(cnt, axes)
        grads = jax.tree.map(lambda g: jax.lax.psum(g, axes) / total_cnt, grads)
        loss = total_nll / total_cnt
        updates, new_opt = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_opt, loss

    # _raw hands back the traceable step for callers embedding it in their
    # own compiled program (the trainer's epoch scan); default is jitted.
    return step if _raw else jax.jit(step, donate_argnums=(0, 1))
