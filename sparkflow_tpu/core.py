"""Compiled train/predict steps: the compute core of the framework.

This replaces the reference's per-worker ``tf.Session`` hot loop
(``sparkflow/HogwildSparkModel.py:38-100``), which per mini-batch paid 1-2 HTTP
round-trips carrying the full model plus ``len(trainables)`` separate ``sess.run``
gradient evals, with a single XLA-compiled program:

- :func:`make_train_step` — one optimizer step: ``value_and_grad`` of the masked
  mean per-example loss, optax update, parameter apply. Everything fuses into one
  XLA executable; gradients never leave the device.
- :func:`make_epoch_fn` — a whole epoch as ONE compiled call: on-device shuffle,
  ``lax.scan`` over fixed-shape mini-batches. Zero host round-trips inside the
  epoch (the reference's design point was one HTTP GET+POST *per batch*).
- :func:`make_predict_fn` — chunked batched inference (the reference ran one giant
  ``sess.run`` over the entire partition, ``sparkflow/ml_util.py:69-73`` — an OOM
  hazard; here chunks are fixed-shape so XLA compiles once).

Static shapes everywhere: batches are padded to a fixed size and masked. Padded
rows contribute zero loss and zero gradient (masked mean), so numerics match
ragged batching.

When a :class:`jax.sharding.Mesh` is supplied, batches are sharded over the
``'dp'`` mesh axis and params/optimizer state are replicated; XLA inserts the
gradient all-reduce over ICI automatically — this all-reduce IS the distributed
communication backend that replaces the reference's Flask/pickle parameter server
(``sparkflow/HogwildSparkModel.py:175-244``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .analysis.runtime_guards import trace_probe
from .graphdef import GraphModel
from .sharding import ShardingConfig, as_sharding_config


def _masked_mean(loss_vec: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.sum(loss_vec * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _rows(x) -> int:
    """Row count of a features value (one array or a tuple of arrays)."""
    return jax.tree.leaves(x)[0].shape[0]


def make_loss_fn(model: GraphModel, input_name,
                 label_name: Optional[str],
                 with_metrics: bool = False) -> Callable:
    """Build ``loss_fn(params, x, y, mask, rng) -> scalar`` from a GraphModel.

    ``with_metrics`` asks for the step's counters as well: where the model
    has a ``loss_and_metrics`` (a registry model with counters of its own,
    ``models/sparse_moe_lm.py``), ``loss_fn`` returns ``(scalar, metrics)``
    and carries ``has_metrics = True``, which :func:`_step_body` reads; any
    other model gives the plain ``loss_fn``.

    ``input_name`` is one tensor name or a sequence of names — with a
    sequence, ``x`` is a matching tuple of arrays (multi-input models, e.g. a
    transformer fed ``input_ids`` + ``attention_mask``).

    ``label_name=None`` is the unsupervised path (reference ``tfLabel=None``,
    e.g. the autoencoder example). The dropout placeholder is deliberately NOT
    fed during training — its graph default applies, matching the reference
    where workers feed only input+label while training
    (``sparkflow/ml_util.py:109-118``) and the dropout feed exists only on the
    predict path (``sparkflow/ml_util.py:70-71``)."""
    build_feeds = make_feeds_builder(input_name, label_name)

    if with_metrics and hasattr(model, "loss_and_metrics"):
        def loss_fn(params, x, y, mask, rng):
            lv, metrics = model.loss_and_metrics(
                params, build_feeds(x, y), train=True, rng=rng)
            with jax.named_scope("batch"):
                return _masked_mean(lv, mask), metrics

        loss_fn.has_metrics = True
        return loss_fn

    def loss_fn(params, x, y, mask, rng):
        lv = model.loss_vector(params, build_feeds(x, y), train=True, rng=rng)
        with jax.named_scope("batch"):
            return _masked_mean(lv, mask)

    return loss_fn


def make_feeds_builder(input_name, label_name: Optional[str]) -> Callable:
    """``(x, y) -> feeds dict`` shared by every step builder: strips ``:0``
    suffixes, zips multi-input tuples, omits the label when unsupervised."""
    multi = isinstance(input_name, (list, tuple))
    in_keys = ([n.split(":")[0] for n in input_name] if multi
               else [input_name.split(":")[0]])
    lbl_key = label_name.split(":")[0] if label_name else None

    def build_feeds(x, y):
        feeds = dict(zip(in_keys, tuple(x) if multi else (x,)))
        if lbl_key is not None:
            feeds[lbl_key] = y
        return feeds

    return build_feeds


def _step_body(loss_fn: Callable, optimizer: optax.GradientTransformation) -> Callable:
    """The one optimizer step shared by make_train_step and make_epoch_fn.

    Its ``jax.named_scope``s take their names from the one list of a step's
    parts, ``utils.tracing.STEP_PARTS`` (``docs/observability.md``, item 3):
    a *part* never lies inside another part, and every device op that the
    step and the model write lies under exactly one, so the parts' times in
    a profile add up to the step's. ``loss`` is a *group* (``STEP_GROUPS``),
    around the loss's ``value_and_grad``: inside it the model names the
    parts (``embed``, ``attn_proj``, ``mlp``, ``lm_head``, ... the same
    names in all four decoder families) and :func:`make_loss_fn` the mean
    over the rows (``batch``), forward and backward, which JAX marks with a
    ``transpose(`` component of its own inside the path; ``optimizer``, the
    update, is a part itself. Nothing of a step lies outside the two.

    A ``loss_fn`` with ``has_metrics`` (:func:`make_loss_fn`) returns the
    step's counters beside the loss; the step then gives ``(loss, metrics)``
    in the loss's place, and the scans over steps and epochs stack both."""
    has_metrics = getattr(loss_fn, "has_metrics", False)

    def step(params, opt_state, x, y, mask, rng):
        with jax.named_scope("loss"):
            loss, grads = jax.value_and_grad(loss_fn, has_aux=has_metrics)(
                params, x, y, mask, rng)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def _sharded_trace_guard(fn: Callable, mesh: Mesh, batch_axis: str = "dp",
                         head_axis: str = "tp") -> Callable:
    """On a >1-device mesh, trace ``fn`` under
    :func:`~sparkflow_tpu.ops.attention.sharded_attention` — pallas custom
    calls have no GSPMD partitioning rule, so sharded programs route
    attention through a nested shard_map over (batch x heads) that runs the
    kernel per shard; shapes that don't divide the mesh fall back to the
    GSPMD-partitionable blockwise path inside flash_attention
    (single-device meshes keep the plain kernel). The axis names must match
    how the caller actually shards the batch/model."""
    if mesh.size <= 1:
        return fn

    from .ops.attention import sharded_attention

    @functools.wraps(fn)
    def guarded(*args):
        with sharded_attention(mesh, batch_axis=batch_axis,
                               head_axis=head_axis):
            return fn(*args)

    return guarded


def make_train_step(loss_fn: Callable, optimizer: optax.GradientTransformation,
                    mesh: Optional[Mesh] = None,
                    infer_params: bool = False,
                    sharding: Optional[ShardingConfig] = None) -> Callable:
    """One jitted optimizer step.

    Signature: ``step(params, opt_state, x, y, mask, rng) ->
    (params, opt_state, loss)``. With a mesh, the batch is sharded over the
    config's data axis ('dp' by default) and XLA all-reduces gradients over
    ICI. ``infer_params=True`` takes param / opt-state shardings from the
    arrays themselves (tp/fsdp-placed params via
    :func:`~sparkflow_tpu.parallel.tp.shard_params`) instead of pinning them
    replicated. ``sharding`` is the declarative
    :class:`~sparkflow_tpu.sharding.ShardingConfig` this wrapper consumes for
    row placement; zero stages >= 1 live in the whole-step shard_map builder
    (:func:`~sparkflow_tpu.parallel.dp.make_dp_train_step`), not here.
    """
    step = trace_probe(_step_body(loss_fn, optimizer), "train_step")

    if mesh is None:
        return jax.jit(step, donate_argnums=(0, 1))

    cfg = as_sharding_config(sharding).validate(mesh, require_data_axis=False)
    step = _sharded_trace_guard(step, mesh)
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, _rows_spec(mesh, cfg))
    pspec = None if infer_params else repl
    return jax.jit(
        step,
        in_shardings=(pspec, pspec, data, data, data, repl),
        out_shardings=(pspec, pspec, repl),
        donate_argnums=(0, 1),
    )


def _rows_spec(mesh: Mesh, sharding: Optional[ShardingConfig] = None) -> P:
    """Batch-row PartitionSpec for ``mesh``: the config's data axes when the
    mesh has them, replicated otherwise — a strategy mesh like
    ``make_mesh({'pp': 2})`` has no dp axis, and pinning P('dp') there dies
    inside jax with an opaque unknown-axis error (the dp-less fallback lives
    in :meth:`ShardingConfig.data_spec`)."""
    return as_sharding_config(sharding).data_spec(mesh)


def _jit_epoch_like(fn: Callable, mesh: Optional[Mesh],
                    infer_params: bool = False,
                    opt_shardings=None,
                    param_shardings=None,
                    sharding: Optional[ShardingConfig] = None) -> Callable:
    """Shared jit wrapper for epoch-shaped programs
    ``fn(params, opt_state, data, labels, mask, rng)``. ``infer_params=True``
    leaves param/opt-state shardings to be inferred from the argument arrays
    (sharded-parameter training: tp/fsdp); the default pins them replicated
    (pure dp). ``opt_shardings`` overrides just the opt-state in/out sharding
    with a matching NamedSharding pytree — zero stages >= 1, where the state
    shards over dp; ``param_shardings`` does the same for params — zero
    stage 3, where the flat param tree shards row-wise too. ``sharding``
    supplies the row placement (data/dcn axes)."""
    fn = trace_probe(fn, getattr(fn, "__name__", "epoch_fn"))
    if mesh is None:
        return jax.jit(fn, donate_argnums=(0, 1))
    cfg = as_sharding_config(sharding)
    fn = _sharded_trace_guard(fn, mesh)
    repl = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, _rows_spec(mesh, cfg))  # dataset rows over dp;
    # XLA re-shards each scanned batch and all-reduces gradients over ICI
    pspec = (param_shardings if param_shardings is not None
             else (None if infer_params else repl))
    ospec = opt_shardings if opt_shardings is not None else (
        None if infer_params else repl)
    return jax.jit(
        fn,
        in_shardings=(pspec, ospec, rows, rows, rows, repl),
        out_shardings=(pspec, ospec, repl),
        donate_argnums=(0, 1),
    )


def make_epoch_fn(loss_fn: Callable, optimizer: optax.GradientTransformation,
                  batch_size: int, num_batches: int, mode: str,
                  shuffle: bool, mesh: Optional[Mesh] = None,
                  n_real: Optional[int] = None, _raw: bool = False,
                  infer_params: bool = False,
                  _unroll_budget: Optional[int] = None,
                  step_fn: Optional[Callable] = None,
                  opt_shardings=None,
                  param_shardings=None,
                  sharding: Optional[ShardingConfig] = None) -> Callable:
    """A full epoch as one compiled program.

    ``mode``:
      - ``'sweep'``      — sequential pass over ``num_batches`` fixed slices
                            (reference mode (b), ``sparkflow/HogwildSparkModel.py:72-83``)
      - ``'stochastic'`` — ``num_batches`` batches drawn from a fresh random
                            permutation (reference mode (a), ``:62-71``; sampling
                            without replacement via permutation prefix)
      - ``'full'``       — num_batches == 1 covering the whole (padded) set
                            (reference mode (c), ``:84-92``)

    Signature: ``epoch(params, opt_state, data, labels, mask, rng) ->
    (params, opt_state, losses[num_batches])``. ``data`` is one array — or a
    tuple of arrays for multi-input models — of shape
    ``[num_batches*batch_size, ...]`` (already padded); labels may be a dummy
    array when unsupervised.

    ``step_fn`` swaps the per-batch update for a strategy-specific one with
    the same ``(params, opt_state, x, y, mask, rng) -> (params, opt_state,
    loss)`` signature (the trainer's pp/sp paths run their dedicated step
    builders inside this SAME shuffle/batching program, so strategy fits
    see identical batch order); ``loss_fn`` is ignored when it is given.
    """

    def epoch(params, opt_state, data, labels, mask, rng):
        used = num_batches * batch_size  # may differ from len(data) in stochastic mode
        take = lambda tree, ix: jax.tree.map(
            lambda a: jnp.take(a, ix, axis=0), tree)
        perm_rng, rng = jax.random.split(rng)
        if mode == "stochastic":
            # num_batches independent mini-batches, each sampled without
            # replacement from the n_real REAL rows only (reference:
            # np.random.choice(..., replace=False) per batch,
            # sparkflow/ml_util.py:121-127) — zero-weight padding rows never
            # occupy batch slots, so every batch trains on batch_size real
            # examples (unless the batch exceeds the dataset, where the
            # remainder is masked padding).
            nr = n_real if n_real is not None else _rows(data)

            def batch_idx(r):
                perm = jax.random.permutation(r, nr)
                if batch_size <= nr:
                    return perm[:batch_size]
                filler = jnp.arange(nr, batch_size)  # padded rows, mask == 0
                return jnp.concatenate([perm, filler])

            idx = jax.vmap(batch_idx)(
                jax.random.split(perm_rng, num_batches)).reshape(-1)
            data_e = take(data, idx)
            labels_e = jnp.take(labels, idx, axis=0)
            mask_e = jnp.take(mask, idx, axis=0)
        elif shuffle:
            perm = jax.random.permutation(perm_rng, _rows(data))
            data_e = take(data, perm)
            labels_e = jnp.take(labels, perm, axis=0)
            mask_e = jnp.take(mask, perm, axis=0)
        else:
            data_e, labels_e, mask_e = data, labels, mask

        def reshape_b(a):
            return a[:used].reshape((num_batches, batch_size) + a.shape[1:])

        xb = jax.tree.map(reshape_b, data_e)
        yb, mb = reshape_b(labels_e), reshape_b(mask_e)
        step_rngs = jax.random.split(rng, num_batches)
        step = step_fn if step_fn is not None else _step_body(loss_fn,
                                                              optimizer)

        def body(carry, batch):
            params, opt_state = carry
            x, y, m, r = batch
            params, opt_state, loss = step(params, opt_state, x, y, m, r)
            return (params, opt_state), loss

        # the budget is the caller's TOTAL step count: unrolling this scan
        # inside a still-looped outer (multi-epoch) scan would balloon the
        # program with zero benefit — every op stays in the while loop
        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), (xb, yb, mb, step_rngs),
            unroll=_cpu_unroll(_unroll_budget if _unroll_budget is not None
                               else num_batches))
        return params, opt_state, losses

    if _raw:
        return epoch
    return _jit_epoch_like(epoch, mesh, infer_params, opt_shardings,
                           param_shardings, sharding)


# XLA:CPU runs large ops (convolutions especially) inside while loops ~30x
# slower than the same ops at top level — measured 0.98s/step standalone vs
# 27s/step inside lax.scan for the batch-1024 MNIST CNN. TPU has no such
# cliff, and the fused scan program is the TPU fast path, so the workaround
# is CPU-only: fully unroll epoch scans when the trip count is small enough
# that compile time stays bounded. Numerics are identical either way.
_CPU_UNROLL_MAX = 32


def _cpu_unroll(length: int):
    if length <= _CPU_UNROLL_MAX and jax.default_backend() == "cpu":
        return True
    return 1


def make_multi_epoch_fn(loss_fn: Callable,
                        optimizer: optax.GradientTransformation,
                        batch_size: int, num_batches: int, mode: str,
                        shuffle: bool, n_epochs: int,
                        mesh: Optional[Mesh] = None,
                        n_real: Optional[int] = None,
                        infer_params: bool = False,
                        step_fn: Optional[Callable] = None,
                        opt_shardings=None,
                        param_shardings=None,
                        sharding: Optional[ShardingConfig] = None) -> Callable:
    """``n_epochs`` whole epochs as ONE compiled program (``lax.scan`` over
    the epoch body): a full ``fit`` becomes a single device dispatch.

    Eliminates per-epoch host round-trips — the launch overhead the
    per-epoch program still pays once per epoch (and which the reference
    paid once per MINI-BATCH as an HTTP exchange,
    ``sparkflow/HogwildSparkModel.py:57-92``). The trainer uses this fast
    path when nothing host-side (verbose logging, loss callbacks,
    checkpointing, straggler timing) needs per-epoch control.

    Signature: ``run(params, opt_state, data, labels, mask, erngs) ->
    (params, opt_state, losses[n_epochs, num_batches])`` where ``erngs`` is
    the stacked per-epoch rng keys — generated by the caller exactly like
    the per-epoch loop does, so losses match the loop path bit-for-bit.
    """
    body = make_epoch_fn(loss_fn, optimizer, batch_size, num_batches, mode,
                         shuffle, n_real=n_real, _raw=True,
                         _unroll_budget=n_epochs * num_batches,
                         step_fn=step_fn)

    def run(params, opt_state, data, labels, mask, erngs):
        def step(carry, erng):
            p, s = carry
            p, s, losses = body(p, s, data, labels, mask, erng)
            return (p, s), losses

        # both scan levels must unroll together on CPU: an unrolled epoch
        # body inside a while-looped epoch scan still puts every op in the
        # loop (see _cpu_unroll) — so the budget is TOTAL steps
        (params, opt_state), losses = jax.lax.scan(
            step, (params, opt_state), erngs,
            unroll=_cpu_unroll(n_epochs * num_batches))
        return params, opt_state, losses

    return _jit_epoch_like(run, mesh, infer_params, opt_shardings,
                           param_shardings, sharding)


def pad_to_batches(x: np.ndarray, batch_size: int,
                   num_batches: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
    """Pad rows so len == num_batches*batch_size; return (padded, mask)."""
    n = x.shape[0]
    if num_batches is None:
        num_batches = max(1, -(-n // batch_size))
    total = num_batches * batch_size
    mask = np.zeros((total,), np.float32)
    mask[:n] = 1.0
    if total == n:
        return x, mask
    pad = np.zeros((total - n,) + x.shape[1:], x.dtype)
    return np.concatenate([x, pad], axis=0), mask


def make_predict_fn(model: GraphModel, input_name, output_name: str,
                    dropout_name: Optional[str] = None,
                    dropout_value: float = 1.0,
                    mesh: Optional[Mesh] = None,
                    infer_params: bool = False,
                    sharding: Optional[ShardingConfig] = None) -> Callable:
    """Jitted fixed-shape inference: ``predict(params, x) -> out``.
    ``input_name`` may be a sequence of names; ``x`` is then a tuple.
    With ``mesh``, the batch shards over the config's data axis ('dp' by
    default); arbitrary batch sizes are padded to the axis size internally
    and trimmed on return.
    ``infer_params=True`` takes param shardings from the arrays themselves
    (tp/fsdp-placed params serve IN PLACE) instead of pinning them
    replicated — mirroring :func:`make_train_step`; without it a placed
    tree is rejected at call time (jit sharding-mismatch error)."""
    multi = isinstance(input_name, (list, tuple))
    in_keys = ([n.split(":")[0] for n in input_name] if multi
               else [input_name.split(":")[0]])
    drop_key = dropout_name.split(":")[0] if dropout_name else None

    def predict(params, x):
        feeds = dict(zip(in_keys, tuple(x) if multi else (x,)))
        if drop_key is not None:
            feeds[drop_key] = jnp.asarray(dropout_value, jnp.float32)
        return model.apply(params, feeds, [output_name], train=False)[output_name]

    if mesh is None or mesh.size <= 1:
        return jax.jit(predict)
    cfg = as_sharding_config(sharding)
    predict = _sharded_trace_guard(predict, mesh)
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, _rows_spec(mesh, cfg))
    pspec = None if infer_params else repl
    inner = jax.jit(predict, in_shardings=(pspec, data), out_shardings=data)
    dp = 1
    for a in cfg.batch_axes(mesh):
        dp *= int(mesh.shape[a])

    def padded_predict(params, x):
        # shard divisibility is handled HERE, not by callers: any batch size
        # (probes of 1, ragged tails, empty) pads up to a dp multiple and
        # trims after — predict_in_chunks needs no mesh awareness
        xs = tuple(x) if multi else (x,)
        n = xs[0].shape[0]
        pad = (-n) % dp
        if pad:
            xs = tuple(jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]) for a in xs)
        out = inner(params, xs if multi else xs[0])
        return out[:n]

    return padded_predict


def predict_in_chunks(predict_fn: Callable, params, x,
                      chunk_size: int = 4096) -> np.ndarray:
    """Run fixed-shape chunks over arbitrary-length input (pad+trim the tail).
    ``x`` is one array or a tuple of arrays (multi-input models).

    The reference fed the entire partition as one batch
    (``sparkflow/ml_util.py:69-73``); fixed chunks bound memory and compile once.
    """
    multi = isinstance(x, (list, tuple))
    if multi:
        xs = tuple(np.asarray(a) for a in x)
        n = xs[0].shape[0]
        zeros = lambda m: tuple(np.zeros((m,) + a.shape[1:], a.dtype)
                                for a in xs)
        sl = lambda i, j: tuple(a[i:j] for a in xs)
        cat = lambda parts, pad: tuple(
            np.concatenate([p, z], axis=0) for p, z in zip(parts, pad))
    else:
        xs = np.asarray(x)
        n = xs.shape[0]
        zeros = lambda m: np.zeros((m,) + xs.shape[1:], xs.dtype)
        sl = lambda i, j: xs[i:j]
        cat = lambda part, pad: np.concatenate([part, pad], axis=0)
    if n == 0:
        # derive the output rank/dtype from a single zero row so empty
        # partitions concatenate cleanly with non-empty ones
        probe = np.asarray(predict_fn(params, zeros(1)))
        return probe[:0]
    chunk = min(chunk_size, max(1, 1 << (n - 1).bit_length()))
    outs = []
    i = 0
    while i < n:
        part = sl(i, i + chunk)
        have = (part[0] if multi else part).shape[0]
        if have < chunk:
            out = np.asarray(predict_fn(params,
                                        cat(part, zeros(chunk - have))))[:have]
        else:
            out = np.asarray(predict_fn(params, part))
        outs.append(out)
        i += chunk
    return np.concatenate(outs, axis=0)
