"""Synchronous TPU trainer — the replacement for the Hogwild parameter server.

The reference's training runtime (``sparkflow/HogwildSparkModel.py``) spawns a
Flask HTTP parameter server on the driver and has every Spark partition run
``iters`` epochs over partition-local data, exchanging full pickled weight/gradient
payloads per mini-batch. Here the same user-facing knobs (``iters``,
``miniBatchSize``, ``miniStochasticIters``, ``shufflePerIter``,
``partitionShuffles``, ``verbose``, ``loss_callback``) drive a synchronous
data-parallel trainer: the union of partition data is staged onto the device mesh
once, and each epoch is a single XLA-compiled program (shuffle + ``lax.scan`` over
fixed-shape mini-batches) with gradient all-reduce over ICI.

Semantics mapping (documented intentional drift from async Hogwild — the north
star mandates synchronous all-reduce):

- ``iters``             -> epochs over the global dataset (reference: epochs over
                           each partition's local shard, concurrent+async).
- ``miniBatchSize``     -> the global batch size per synchronous step.
- ``miniStochasticIters``-> stochastic mini-batch steps per epoch (drawn from a
                           fresh permutation, i.e. without replacement — matching
                           ``np.random.choice(..., replace=False)`` in
                           ``sparkflow/ml_util.py:121-127``).
- ``partitionShuffles`` -> outer repeats of the whole ``iters`` loop (the
                           reference reshuffles partitions between rounds,
                           ``HogwildSparkModel.py:258-266``; here data is
                           re-permuted on device every epoch anyway).
- ``acquireLock``       -> accepted, no-op: synchronous updates are already
                           serialized; there is no shared mutable server state.
- Convergence semantics therefore differ from lock-free Hogwild by design;
  the update rule equals the reference's ``acquireLock=True`` path with
  simultaneous gradient arrival (sum/mean of worker gradients).
"""

from __future__ import annotations

import contextlib
import logging
import os
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .core import (make_epoch_fn, make_loss_fn, make_multi_epoch_fn,
                   make_predict_fn, pad_to_batches)
from .graphdef import GraphDef, GraphModel, params_to_list
from .optimizers import build_optimizer
from .parallel.mesh import replicate_on_mesh
from .sharding import ShardingConfig, as_sharding_config

logger = logging.getLogger("sparkflow_tpu")



def _ckpt_state(params, opt_state, step, rng, *, rng_impl):
    """The checkpoint payload schema — single source of truth for every
    save/restore site in fit and fit_stream. Typed PRNG keys (rng_impl set)
    checkpoint as their raw key data; _restore_rng re-wraps them. The impl
    NAME rides along as an ASCII uint8 array (orbax/npz-safe) so restore can
    compare it exactly — 'rbg' and 'unsafe_rbg' have identical key-data
    widths, so width alone cannot tell them apart."""
    import jax.dtypes
    if hasattr(rng, "dtype") and jax.dtypes.issubdtype(rng.dtype,
                                                       jax.dtypes.prng_key):
        rng = jax.random.key_data(rng)
    impl = np.frombuffer((rng_impl or "threefry").encode(), dtype=np.uint8)
    return {"params": params, "opt_state": opt_state,
            "epoch": np.int64(step), "rng": np.asarray(rng),
            "rng_impl": impl.copy()}


class TrainResult:
    """Outcome of a fit: final params + per-epoch mean losses.

    ``stop_reason`` says how the fit ended — ``'completed'`` (ran every
    planned epoch), ``'preempted'`` (SIGTERM checkpoint-and-return; resuming
    on the same checkpoint_dir finishes the run), or ``'nan'``
    (halt_on_nan tripped). ``resilience.run_resilient_fit`` keys its restart
    decision off this field.
    """

    __slots__ = ("params", "losses", "examples_per_sec", "wall_time_s",
                 "stop_reason", "metrics")

    def __init__(self, params, losses, examples_per_sec, wall_time_s,
                 stop_reason: str = "completed", metrics=None):
        self.params = params
        self.losses = losses
        self.examples_per_sec = examples_per_sec
        self.wall_time_s = wall_time_s
        self.stop_reason = stop_reason
        # the model's own counters of every step of a fused fit, as numpy
        # arrays ``[epochs, steps, ...]`` read back once after the fit
        # (``loss_and_metrics``: models/sparse_moe_lm.py); None otherwise
        self.metrics = metrics

    @property
    def completed(self) -> bool:
        return self.stop_reason == "completed"


class Trainer:
    """Single-controller synchronous trainer over an optional device mesh.

    Parameters mirror the reference estimator's training Params
    (``sparkflow/tensorflow_async.py:104-121``); ``mesh`` is the TPU-native
    addition — a ``jax.sharding.Mesh`` whose ``'dp'`` axis shards the batch.
    """

    def __init__(self,
                 graph: Union[str, GraphDef, GraphModel],
                 input_name: str,
                 label_name: Optional[str] = None,
                 optimizer: Union[str, optax.GradientTransformation] = "adam",
                 learning_rate: float = 0.01,
                 optimizer_options: Optional[Dict[str, Any]] = None,
                 iters: int = 1000,
                 mini_batch_size: int = 128,
                 mini_stochastic_iters: int = -1,
                 shuffle_per_iter: bool = True,
                 partition_shuffles: int = 1,
                 verbose: int = 0,
                 loss_callback: Optional[Callable] = None,
                 dropout_name: Optional[str] = None,
                 acquire_lock: bool = False,  # accepted for API parity; no-op
                 mesh=None,
                 seed: int = 0,
                 compute_dtype=None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0,
                 publish_to=None,
                 publish_every: int = 0,
                 resume_retries: int = 2,
                 straggler_factor: Optional[float] = None,
                 straggler_callback: Optional[Callable] = None,
                 metrics=None,
                 param_sharding: Union[str, None, dict] = "auto",
                 rng_impl: Optional[str] = None,
                 halt_on_nan: bool = False,
                 pp_microbatches: Optional[int] = None,
                 pp_schedule: str = "gpipe",
                 weight_update_sharding: str = "auto",
                 debug_recompiles: bool = False,
                 strategy: Optional[str] = None,
                 elastic: Optional[Dict[str, Any]] = None,
                 sharding: Union[ShardingConfig, dict, None] = None):
        if isinstance(graph, GraphDef):
            self.model = GraphModel(graph, compute_dtype)
        elif isinstance(graph, str):
            from .models import model_from_json
            self.model = model_from_json(graph, compute_dtype)
        else:  # an executable model object (GraphModel or registry model)
            self.model = graph
        # fail fast on bad tensor names (otherwise they surface later as a
        # confusing "placeholder not fed" error from the executor).
        # input_name may be a sequence of tensor names (multi-input models,
        # e.g. input_ids + attention_mask) — features then travel as a tuple.
        for name in (input_name if isinstance(input_name, (list, tuple))
                     else [input_name]):
            self.model.graphdef.resolve(name)
        if label_name:
            self.model.graphdef.resolve(label_name)
        if dropout_name:
            self.model.graphdef.resolve(dropout_name)
        self.input_name = input_name
        self.label_name = label_name
        if isinstance(optimizer, str):
            self.optimizer = build_optimizer(optimizer, learning_rate, optimizer_options)
            self._opt_cfg = dict(optimizer_options or {})
        else:
            self.optimizer = optimizer
            # optax object: optimizer_options (when the caller passes it
            # alongside, as the estimator does) still informs the zero1
            # 'auto' gate; otherwise the object is opaque
            self._opt_cfg = (dict(optimizer_options) if optimizer_options
                             else None)
        self.iters = iters
        self.mini_batch_size = mini_batch_size
        self.mini_stochastic_iters = mini_stochastic_iters
        self.shuffle_per_iter = shuffle_per_iter
        self.partition_shuffles = max(1, partition_shuffles)
        self.verbose = verbose
        self.loss_callback = loss_callback
        self.dropout_name = dropout_name
        self.mesh = mesh
        self.seed = seed
        # rng_impl='rbg' swaps the dropout/shuffle key stream to the TPU's
        # hardware PRNG (typed keys carry their impl through split/fold_in/
        # bernoulli): threefry mask generation is pure VPU overhead on the
        # training step — dropout-heavy transformers reclaim it. None keeps
        # JAX's default threefry stream (bit-reproducible with prior rounds).
        self.rng_impl = rng_impl
        # pipeline-parallel fits ('pp' mesh axis): microbatches per batch
        # (None = deepest power-of-two the per-replica batch divides) and
        # schedule ('gpipe' | '1f1b' | 'sequential' — parallel/pp.py)
        self.pp_microbatches = pp_microbatches
        self.pp_schedule = pp_schedule
        # ZeRO-1 weight-update sharding on pure-dp meshes (optimizers_sharded):
        # 'auto' turns on when the optimizer carries per-param state and
        # dp >= 2 (and nothing standard-layout-dependent like clip_norm /
        # ema_decay is configured); 'on' forces it where eligible (warns and
        # falls back otherwise); 'off' keeps the replicated update
        if weight_update_sharding not in ("auto", "on", "off"):
            raise ValueError(
                f"weight_update_sharding must be 'auto', 'on', or 'off'; "
                f"got {weight_update_sharding!r}")
        self.weight_update_sharding = weight_update_sharding
        # the declarative ShardingConfig (sharding.py) supersedes the legacy
        # knob when given: its zero_stage (0-3) is an explicit request —
        # ineligible fits raise instead of silently falling back — and its
        # data/dcn axes + offload flag drive the unified dp step builder.
        # None keeps the weight_update_sharding semantics above.
        self.sharding = (as_sharding_config(sharding)
                         if sharding is not None else None)
        # training strategy: None/'sync' is the synchronous mesh path below;
        # 'elastic_dp' routes fit() through parallel.elastic — bounded-
        # staleness async replicas over a versioned parameter store (the
        # reference's Hogwild identity, modernized). `elastic` tunes it:
        # replicas, max_staleness, dampening, density_threshold, lease_ttl_s.
        if strategy not in (None, "sync", "elastic_dp"):
            raise ValueError(
                f"strategy must be None, 'sync', or 'elastic_dp'; "
                f"got {strategy!r}")
        self.strategy = strategy
        self.elastic = dict(elastic or {})
        _known = {"replicas", "max_staleness", "dampening",
                  "density_threshold", "lease_ttl_s"}
        unknown = set(self.elastic) - _known
        if unknown:
            raise ValueError(
                f"unknown elastic option(s) {sorted(unknown)}; "
                f"known: {sorted(_known)}")
        if self.elastic and strategy != "elastic_dp":
            raise ValueError(
                "elastic options require strategy='elastic_dp'")
        # filled by an elastic fit: push/staleness/membership accounting
        self.last_elastic_stats: Optional[Dict[str, Any]] = None
        # debug_recompiles=True runs each fit under analysis.track_recompiles:
        # every train/epoch-step trace is counted and diffed, and the report
        # lands in self.recompile_report / self.recompile_findings
        self.debug_recompiles = bool(debug_recompiles)
        self.recompile_report: Optional[str] = None
        self.recompile_findings: list = []
        self._zero1_active = False
        self._zero_stage = 0        # resolved per fit: 0..3
        self._zero3_template = None  # standard param shapes for stage-3 fits
        self._offload_active = False
        # divergence detection: a non-finite epoch loss always WARNS
        # (post-hoc on the fused path); halt_on_nan=True additionally stops
        # the fit at that epoch, returning the state from before the NaN
        # update propagated further — it joins verbose/loss_callback/
        # checkpointing in the needs-per-epoch-host-control set, so setting
        # it takes the loop path instead of the single-dispatch fused one
        self.halt_on_nan = halt_on_nan
        self.params = None
        self._last_opt_state = None
        self._epoch_cache = {}  # (batch, num_batches, mode, shuffle) -> compiled epoch
        self._state_programs = {}  # (with_opt, replicate) -> jitted _fresh_state
        # step-level checkpoint/resume — a capability upgrade over the
        # reference's save-at-end-only persistence (SURVEY.md §5)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        # live weight publication (train→serve): unlike checkpoints, which
        # exist to restore *this* trainer, a publish hands the current
        # weights to serving replicas (WeightWatcher hot-swap) — so it is
        # independent of checkpoint_dir. publish_every == 0 means
        # publish-once-at-fit-end when a store is configured.
        self.publish_every = int(publish_every)
        if isinstance(publish_to, str):
            from .serving.weightstore import WeightStore
            publish_to = WeightStore(publish_to)
        self._publish_store = publish_to
        # pod-scale failure handling (SURVEY.md §5: the reference's
        # drop-the-update-and-print "is not acceptable at pod scale"):
        # with a checkpoint_dir configured, a failing epoch auto-restores the
        # last checkpoint and continues, up to resume_retries times.
        # straggler_factor (e.g. 3.0) opts into per-epoch heartbeat timing:
        # an epoch slower than factor x the running median logs a warning,
        # emits a metric, and calls straggler_callback(epoch, secs, median).
        self.resume_retries = resume_retries
        self.straggler_factor = straggler_factor
        self.straggler_callback = straggler_callback
        # Sharded-parameter training (tp/fsdp): "auto" derives PartitionSpecs
        # from the model when the mesh has tensor axes beyond 'dp'
        # (megatron rules via model.param_pspecs(), ZeRO via fsdp_pspecs);
        # an explicit pspec pytree is used as-is; None keeps params
        # replicated (pure dp). See parallel/tp.py:derive_param_pspecs.
        self.param_sharding = param_sharding
        if metrics is None:
            from .utils.metrics import default_metrics
            metrics = default_metrics
        self.metrics = metrics
        # span tracing (obs/): fit(trace_spans=...) fills these per run —
        # the Tracer holding the spans, the StepStats phase summary, and
        # the Chrome-trace path the fit exported
        self.last_tracer = None
        self.last_step_stats: Optional[dict] = None
        self.last_trace_path: Optional[str] = None
        self._tracer = None
        self._step_stats = None

    # -- batching plan ------------------------------------------------------

    def _resolve_pspecs(self):
        """PartitionSpec pytree for sharded-parameter training, or None.
        Only meaningful on a multi-device mesh with tensor axes beyond 'dp'
        (pure-dp meshes replicate params regardless)."""
        if self.mesh is None:
            return None
        if self._mesh_strategy() != "default":
            # pp/sp fits derive their placements in fit() (pp: pp_pspecs on
            # the stage layout; sp: replicated params), not from megatron/
            # ZeRO rules; _strategy_task refuses an explicit user pytree
            return None
        if self.sharding is not None and self.sharding.param_axes != "auto":
            # the declarative config's per-param placement supersedes the
            # legacy param_sharding knob: None -> replicated, a pytree ->
            # explicit PartitionSpecs ('auto' defers to the knob below)
            pa = self.sharding.param_axes
            if pa is not None and isinstance(pa, str):
                raise ValueError(
                    f"ShardingConfig.param_axes must be 'auto', None, or a "
                    f"PartitionSpec pytree; got {pa!r}")
            return pa
        if self.param_sharding is None:
            return None
        if not isinstance(self.param_sharding, str):
            return self.param_sharding  # explicit pspec pytree
        if self.param_sharding != "auto":
            raise ValueError(
                f"param_sharding must be 'auto', None, or a PartitionSpec "
                f"pytree; got {self.param_sharding!r}")
        if all(a == "dp" for a in self.mesh.axis_names):
            return None
        from .parallel.tp import derive_param_pspecs
        pspecs = derive_param_pspecs(self.model, self.mesh)
        if pspecs is None and any(a_ in self.mesh.axis_names
                                  for a_ in ("tp", "ep")):
            # refusing beats silently replicating params and letting the
            # tensor ranks compute redundant identical work
            raise ValueError(
                f"mesh axes {[a_ for a_ in self.mesh.axis_names if a_ != 'dp']} "
                f"request tensor-sharded params but "
                f"{type(self.model).__name__} publishes no param_pspecs() "
                f"(megatron rules exist for the registry transformer/resnet/"
                f"moe families); use an 'fsdp' axis instead — ZeRO specs "
                f"derive from param_specs() for any model")
        return pspecs

    def _fresh_state(self, tree, *, with_opt: bool, replicate: bool = False):
        """What a fit starts from, made by ONE device program over the whole
        tree and not by a handful of eager ones a leaf: the fit's own copy of
        ``tree`` and, ``with_opt``, the optimizer's fresh state for that copy
        (returned as ``(copy, state)``). The copy is a real one, ``jnp.copy``
        of every leaf *inside* the jit: a jitted identity may forward its
        input buffers, and the epoch program donates its params and state, so
        it must never be handed arrays a caller still holds.

        ``replicate`` pins both outputs on the trainer's mesh, replicated, as
        :func:`replicate_on_mesh` leaves them (pure dp/sp): under jit a
        ``zeros_like`` has no data dependence on its parameter, so the
        placement is stated, never left to the compiler. The programs are
        built once and kept; ``jax.jit`` caches by tree structure and avals."""
        prog = self._state_programs.get((with_opt, replicate))
        if prog is None:
            optimizer = self.optimizer  # the kept program holds no trainer

            def init_state(src):
                params = jax.tree.map(jnp.copy, src)
                if not with_opt:
                    return params
                return params, optimizer.init(params)

            from jax.sharding import NamedSharding, PartitionSpec as P
            prog = self._state_programs[(with_opt, replicate)] = jax.jit(
                init_state, out_shardings=(NamedSharding(self.mesh, P())
                                           if replicate else None))
        if replicate:
            # arrays committed elsewhere (another trainer's mesh) move first;
            # what is already replicated here passes through untouched
            tree = replicate_on_mesh(tree, self.mesh)
        return prog(tree)

    def _place_params(self, params, pspecs):
        from .parallel.tp import shard_params
        return shard_params(params, self.mesh, pspecs)

    # -- pp/sp strategy dispatch -------------------------------------------
    # 'pp'/'sp' mesh axes train through the dedicated step builders
    # (parallel.pp / parallel.sp) slotted into the SAME epoch machinery via
    # its step_fn override, so strategy fits see identical shuffle/batch
    # order to the default path — this is what makes them reachable from
    # the estimator's meshShape Param (reference has no parallelism at all;
    # SURVEY.md §2.3).

    def _mesh_strategy(self) -> str:
        if self.mesh is None:
            return "default"
        axes = self.mesh.axis_names
        if "pp" in axes and "sp" in axes:
            raise ValueError(
                "a Trainer mesh cannot combine 'pp' and 'sp' axes; pick "
                "one strategy per fit (pipeline xor sequence parallelism)")
        if "pp" in axes:
            bad = [a_ for a_ in axes if a_ not in ("pp", "dp")]
            if bad:
                raise ValueError(
                    f"'pp' composes with 'dp' only; mesh also has {bad}")
            return "pp"
        if "sp" in axes:
            bad = [a_ for a_ in axes if a_ not in ("sp", "dp")]
            if bad:
                raise ValueError(
                    f"'sp' composes with 'dp' only; mesh also has {bad}")
            return "sp"
        return "default"

    def _strategy_task(self, strategy: str) -> str:
        """Validate the model/mesh/label combination for a pp or sp fit and
        return the step-builder task ('classifier' | 'lm')."""
        m = self.model
        # pipeline stages / replicated sp params are placed by the strategy
        # itself — an explicit user pytree cannot be honored, so refuse it
        # loudly rather than silently replicating
        if (self.param_sharding is not None
                and not isinstance(self.param_sharding, str)):
            raise ValueError(
                "explicit param_sharding pytrees do not apply to pp/sp "
                "strategy meshes (the strategy places its own params); "
                "drop param_sharding or use a dp/tp/fsdp/ep mesh")
        n_inputs = (len(self.input_name)
                    if isinstance(self.input_name, (list, tuple)) else 1)
        if strategy == "pp" and self.label_name is not None and n_inputs != 1:
            raise ValueError(
                "pp classifier fits take exactly one input tensor (the "
                "token ids); the pipeline step has no attention-mask path — "
                "extra inputs would be silently ignored, so refuse instead")
        if n_inputs > 2:
            raise ValueError(
                f"{strategy} fits take at most (input_ids, attention_mask); "
                f"got {n_inputs} input tensors")
        if strategy == "pp":
            if not (hasattr(m, "num_layers") and hasattr(m, "_block")):
                raise ValueError(
                    f"meshShape with a 'pp' axis trains the registry "
                    f"transformer families (stage-shardable blocks); "
                    f"{type(m).__name__} has no block structure to "
                    f"pipeline — use dp/fsdp for nn-DSL graphs")
            n_stages = self.mesh.shape["pp"]
            if m.num_layers % n_stages:
                raise ValueError(
                    f"num_layers={m.num_layers} does not divide into "
                    f"pp={n_stages} pipeline stages")
            return "lm" if self.label_name is None else "classifier"
        # sp: ring attention is causal-LM only (boundary-token exclusion
        # is next-token-loss math; see parallel/sp.py docstring)
        from .models.transformer import TransformerLM
        if not isinstance(m, TransformerLM):
            raise ValueError(
                f"meshShape with an 'sp' axis trains causal LM registry "
                f"models (ring attention over the sequence); "
                f"{type(m).__name__} is not a TransformerLM family model")
        if self.label_name is not None:
            raise ValueError(
                "'sp' fits are unsupervised next-token training "
                "(tfLabel/label_name must be None)")
        return "lm"

    def _make_strategy_step(self, strategy: str, task: str, batch: int):
        """The per-batch step_fn for the epoch machinery: wraps the pp/sp
        builder's raw step under unsharded_attention (they run their own
        shard_map; re-wrapping the kernel over the same axes is invalid)."""
        from .ops.attention import unsharded_attention
        from .parallel.mesh import mesh_axis_size
        dp = mesh_axis_size(self.mesh, "dp")
        if batch % max(dp, 1):
            raise ValueError(
                f"mini_batch_size={batch} must divide over the dp axis "
                f"(size {dp}) for a {strategy} fit")
        if strategy == "pp":
            from .parallel.pp import make_pp_train_step
            per_dp = batch // max(dp, 1)
            M = self.pp_microbatches
            if M is None:
                # auto: deepest power-of-two microbatching the per-replica
                # batch supports (bounds pipeline bubble at fixed memory)
                M = next(m for m in (8, 4, 2, 1) if per_dp % m == 0)
            elif per_dp % M:
                raise ValueError(
                    f"pp_microbatches={M} must divide the per-dp-replica "
                    f"batch {per_dp}")
            raw = make_pp_train_step(
                self.model, self.optimizer, self.mesh, n_microbatches=M,
                schedule=self.pp_schedule, task=task, _raw=True)

            def step_fn(p, o, x, y, m, r):
                ids = x[0] if isinstance(x, tuple) else x
                # lm task consumes the attention mask as token loss weights
                y_eff = (x[1] if task == "lm" and isinstance(x, tuple)
                         else y)
                with unsharded_attention():
                    return raw(p, o, ids, y_eff, r)

            return step_fn
        from .parallel.sp import make_sp_train_step
        sp = self.mesh.shape["sp"]
        raw = make_sp_train_step(self.model, self.optimizer, self.mesh,
                                 _raw=True)

        def step_fn(p, o, x, y, m, r):
            ids = x[0] if isinstance(x, tuple) else x
            amask = x[1] if isinstance(x, tuple) else y  # y carries ones
            if ids.shape[1] % sp:
                raise ValueError(
                    f"sequence length {ids.shape[1]} must divide the sp "
                    f"axis (size {sp}) for ring attention")
            with unsharded_attention():
                return raw(p, o, ids, amask, r)

        return step_fn

    def _data_axis(self) -> str:
        return (self.sharding.data_axis if self.sharding is not None
                else "dp")

    def _dp_size(self) -> int:
        from .parallel.mesh import mesh_axis_size
        return mesh_axis_size(self.mesh, self._data_axis())

    # -- ZeRO weight-update/param sharding (optimizers_sharded) -------------

    def _active_cfg(self) -> ShardingConfig:
        """The ShardingConfig in effect for the current fit: the explicit
        one when given, else the legacy knobs mapped onto a config — with
        ``zero_stage`` pinned to what :meth:`_resolve_zero_stage` decided."""
        base = (self.sharding if self.sharding is not None
                else ShardingConfig())
        return base.replace(zero_stage=self._zero_stage)

    def _resolve_zero_stage(self, strategy: str, pspecs, params) -> int:
        """Decide how much of the weight update shards over dp (zero stage
        0-3).

        Eligible: default (pure-dp) strategy, replicated params (on tp/fsdp
        meshes the opt state already shards WITH the params — a zero stage
        would be a no-op at best), and dp >= 2. The legacy
        ``weight_update_sharding`` knob maps 'off'->0 and 'on'/'auto'->1:
        'auto' additionally requires the optimizer to carry per-param state
        (there is nothing to shard for sgd) and declines when clip_norm /
        ema_decay are configured — the global-norm clip would measure only
        its shard's norm, and EMA extraction expects the standard layout.
        An explicit ``sharding=ShardingConfig(zero_stage=N)`` is a REQUEST:
        ineligible fits raise an actionable ValueError instead of silently
        falling back.
        """
        cfg_opts = self._opt_cfg or {}
        blocked = [k for k in ("clip_norm", "ema_decay") if cfg_opts.get(k)]
        eligible = (strategy == "default" and pspecs is None
                    and self.mesh is not None
                    and self._data_axis() in self.mesh.axis_names
                    and self._dp_size() >= 2)
        if self.sharding is not None:
            stage = self.sharding.zero_stage
            if stage == 0:
                return 0
            if self.mesh is None:
                raise ValueError(
                    f"sharding.zero_stage={stage} shards the update over "
                    f"mesh axis {self.sharding.data_axis!r} but the trainer "
                    f"has no mesh; pass mesh=make_mesh({{'"
                    f"{self.sharding.data_axis}': N}}) or use zero_stage=0")
            # dp-less / undersized mesh: the config's own validation message
            self.sharding.validate(self.mesh, require_data_axis=True)
            if not eligible:
                raise ValueError(
                    f"sharding.zero_stage={stage} needs a pure-dp fit with "
                    f"replicated params and {self.sharding.data_axis} >= 2 "
                    f"(got strategy={strategy!r}, sharded-params="
                    f"{pspecs is not None}, {self.sharding.data_axis}="
                    f"{self._dp_size()}); use zero_stage=0 or a "
                    f"{self.sharding.data_axis}-axis mesh")
            if blocked:
                raise ValueError(
                    f"sharding.zero_stage={stage} is incompatible with "
                    f"optimizer options {blocked}: the shard-local update "
                    f"would break their global-layout math (clip_norm "
                    f"measures a global norm; ema extraction expects the "
                    f"standard layout)")
            return stage
        mode = self.weight_update_sharding
        if mode == "off":
            return 0
        if mode == "on":
            if not eligible:
                logger.warning(
                    "weight_update_sharding='on' needs a pure-dp fit on a "
                    "mesh with dp >= 2 (got strategy=%r, sharded-params=%s, "
                    "dp=%d); training with the replicated update", strategy,
                    pspecs is not None, self._dp_size())
                return 0
            if blocked:
                logger.warning(
                    "weight_update_sharding='on' is incompatible with %s "
                    "(shard-local update would break their global-layout "
                    "math); training with the replicated update", blocked)
                return 0
            return 1
        # auto
        if not eligible or blocked:
            return 0
        from .optimizers_sharded import has_per_param_state
        return 1 if has_per_param_state(self.optimizer, params) else 0

    def _make_zero_step(self, param_template=None):
        """The per-batch step_fn for the epoch machinery: the raw unified dp
        stepper (stage 1-3) runs its own shard_map, so — exactly like the
        pp/sp strategy steps — it must run under unsharded_attention
        (re-wrapping the attention kernel over the same mesh axes is
        invalid)."""
        from .ops.attention import unsharded_attention
        from .parallel.dp import make_dp_train_step
        raw = make_dp_train_step(self.model, self.optimizer, self.mesh,
                                 self.input_name, self.label_name,
                                 sharding=self._active_cfg(),
                                 param_template=param_template, _raw=True)

        def step_fn(p, o, x, y, m, r):
            with unsharded_attention():
                return raw(p, o, x, y, m, r)

        return step_fn

    def _wrap_offload(self, epoch_fn, opt_shardings):
        """``sharding.offload_opt_state=True``: the optimizer state's home is
        host memory — double-buffered, not synchronous. The old wrapper
        serialized ``device_put → step → device_get`` every call, stalling
        the loop on a PCIe round-trip per step. Now the first call (and any
        call handed a host tree, e.g. after a checkpoint restore) uploads
        with the step's shardings so donation still sees correctly-placed
        buffers; steady-state calls recognize their own returned device tree
        and skip the re-upload entirely. The device→host copy of step t's
        updated state is *enqueued* right after the (async-dispatched) step
        program, so it completes behind step t+1's compute — checkpoint
        saves, preemption and :meth:`_flush_opt_state` then find the bytes
        already host-side instead of paying the transfer at the sync point.
        Numerics are untouched: no value ever round-trips through a lossy
        path, so losses are bitwise-equal to the on-device run. Only the
        loop paths support it (the fused multi-epoch program never returns
        to the host)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        repl = (NamedSharding(self.mesh, P())
                if self.mesh is not None else None)
        last = {"dev": None}

        def wrapped(params, opt_state, *rest):
            if opt_state is not last["dev"]:
                # cold path: first call, or a restore handed us a host tree
                place = opt_shardings if opt_shardings is not None else (
                    jax.tree.map(lambda _: repl, opt_state)
                    if repl is not None else None)
                if place is not None:
                    opt_state = jax.tree.map(jax.device_put, opt_state,
                                             place)
            params, opt_state, losses = epoch_fn(params, opt_state, *rest)
            # enqueue the D2H copy NOW: it drains while the caller
            # dispatches the next step, not when someone blocks on it
            for leaf in jax.tree.leaves(opt_state):
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()
            last["dev"] = opt_state
            return params, opt_state, losses

        return wrapped

    def _flush_opt_state(self, opt_state):
        """Offload runs keep the working opt state device-resident between
        steps (the host mirror refreshes asynchronously via
        ``copy_to_host_async``); materialize concrete host arrays at the
        points where the state outlives the loop (``_last_opt_state``)."""
        if not self._offload_active or opt_state is None:
            return opt_state
        return jax.tree.map(np.asarray, opt_state)

    def _params_to_ckpt(self, params):
        """Checkpoints (and ``self.params`` / TrainResult) always hold the
        STANDARD param layout; stage-3 fits convert from the flat sharded
        tree. Idempotent: params already in standard shape pass through, so
        post-fit callers (ema_weights) can't double-convert."""
        if self._zero_stage < 3 or self._zero3_template is None:
            return params
        t_leaves = jax.tree.leaves(self._zero3_template)
        p_leaves = jax.tree.leaves(params)
        if all(tuple(p.shape) == tuple(t.shape)
               for p, t in zip(p_leaves, t_leaves)):
            return params
        from .optimizers_sharded import gather_zero3_params
        return gather_zero3_params(params, self._zero3_template)

    def _params_from_ckpt(self, params):
        """Restore-side inverse of :meth:`_params_to_ckpt`: re-flatten and
        re-shard standard params for THIS mesh's dp size and place them."""
        if self._zero_stage < 3:
            return params
        from .optimizers_sharded import (shard_zero3_params,
                                         zero3_param_shardings)
        dp_n = self._dp_size()
        flat = shard_zero3_params(params, dp_n)
        shards = zero3_param_shardings(flat, self.mesh, dp_n,
                                       self._data_axis())
        return jax.tree.map(jax.device_put, flat, shards)

    def _opt_to_ckpt(self, params, opt_state):
        """Checkpoints always hold the STANDARD (param-shaped) opt state, so
        directories stay interchangeable across zero stages 0-3 and mesh-
        shape changes. ``params`` may arrive in either layout (stage-3 call
        sites hold the flat tree)."""
        if not self._zero1_active:
            return opt_state
        from .optimizers_sharded import gather_zero1_state
        return gather_zero1_state(self.optimizer,
                                  self._params_to_ckpt(params), opt_state,
                                  self._dp_size())

    def _opt_from_ckpt(self, params, opt_state):
        """Restore-side inverse of :meth:`_opt_to_ckpt`: re-pad and re-shard
        the standard state for THIS mesh's dp size (which may differ from
        the writing run's) and place the shards. ``params`` must be the
        STANDARD layout (restore converts the opt state before the stage-3
        param flattening)."""
        if not self._zero1_active:
            return opt_state
        from .optimizers_sharded import place_zero1_state, shard_zero1_state
        dp_n = self._dp_size()
        return place_zero1_state(
            shard_zero1_state(self.optimizer, params, opt_state, dp_n),
            self.mesh, dp_n, self._data_axis())

    def _plan(self, n: int):
        """Resolve (mode, batch_size, num_batches) from the reference's three
        batching modes (``sparkflow/HogwildSparkModel.py:62-92``)."""
        dp = self._dp_size()
        bs = self.mini_batch_size
        stochastic = bool(self.mini_stochastic_iters and self.mini_stochastic_iters > 0)
        if bs is None or bs <= 0 or (bs >= n and not stochastic):
            # full-batch mode; an over-large miniBatchSize degenerates to one
            # full-batch step per epoch...
            batch = -(-n // dp) * dp
            return "full", batch, 1
        if bs >= n:
            # ...except in stochastic mode, where the reference clamps the
            # batch to the dataset and still runs the requested number of
            # steps per epoch (sparkflow/ml_util.py:105-106)
            bs = n
        batch = -(-bs // dp) * dp  # round batch up to a multiple of dp shards
        sweeps = -(-n // batch)
        if self.mini_stochastic_iters and self.mini_stochastic_iters > 0:
            # exactly miniStochasticIters random batches per epoch, even past
            # one sweep of the data (reference ml_util.py:121-127 semantics)
            return "stochastic", batch, self.mini_stochastic_iters
        return "sweep", batch, sweeps

    # -- fit ----------------------------------------------------------------

    def _make_rng(self):
        """Root key for this fit: default threefry, or a typed key on the
        configured ``rng_impl`` (e.g. 'rbg' — see __init__)."""
        if self.rng_impl:
            return jax.random.key(self.seed, impl=self.rng_impl)
        return jax.random.PRNGKey(self.seed)

    def _restore_rng(self, raw, saved_impl=None):
        """Inverse of _ckpt_state's key handling: re-wrap raw key data under
        the configured impl. ``saved_impl`` is the checkpoint's recorded impl
        name (ASCII uint8 array) — compared exactly, so even same-width swaps
        like 'rbg' vs 'unsafe_rbg' fail with an actionable error instead of
        silently continuing on a different key stream. The key-data width
        check remains as a backstop for pre-schema checkpoints."""
        raw = jnp.asarray(raw)
        mine = self.rng_impl or "threefry"
        if saved_impl is not None:
            try:
                theirs = np.asarray(saved_impl,
                                    dtype=np.uint8).tobytes().decode()
            except UnicodeDecodeError:
                raise ValueError(
                    "checkpoint rng_impl record is not valid ASCII — the "
                    "checkpoint is corrupt; point checkpoint_dir at a fresh "
                    "directory to restart the rng stream") from None
            if theirs != mine:
                raise ValueError(
                    f"checkpoint was written under rng_impl={theirs!r} but "
                    f"this trainer is configured with rng_impl={mine!r}: "
                    f"resume with the matching rng_impl, or point "
                    f"checkpoint_dir at a fresh directory to restart the "
                    f"rng stream")
        expect = 4 if self.rng_impl in ("rbg", "unsafe_rbg") else 2
        got = raw.shape[-1] if raw.ndim else None
        if got != expect:
            raise ValueError(
                f"checkpoint rng has {got} key-data words but rng_impl="
                f"{self.rng_impl!r} expects {expect}: this checkpoint_dir was "
                f"written under a different rng_impl — resume with the "
                f"matching rng_impl, or point checkpoint_dir at a fresh "
                f"directory to restart the rng stream")
        if self.rng_impl:
            return jax.random.wrap_key_data(raw, impl=self.rng_impl)
        return raw

    def _ckpt_restore(self, ckpt_mgr, ckpt_like):
        """``ckpt_mgr.restore`` with pre-schema back-compat: checkpoints
        written before the ``rng_impl`` leaf existed fail a template restore
        that includes it (orbax raises an opaque structure-mismatch error),
        so retry without the leaf — _restore_rng's key-data width check then
        covers the impl validation for those legacy checkpoints."""
        try:
            return ckpt_mgr.restore(like=ckpt_like)
        except Exception as e:
            # only fall back when the SAVED tree genuinely lacks the leaf —
            # a new-schema checkpoint whose restore failed for a real reason
            # (corruption, shape change) must surface its original error,
            # not silently skip the exact-impl validation
            try:
                raw = ckpt_mgr.restore()
            except Exception:
                raise e
            if not isinstance(raw, dict) or "rng_impl" in raw:
                raise e
            logger.warning(
                "checkpoint in %s predates the rng_impl schema; restoring "
                "without it (impl validated by key-data width only)",
                self.checkpoint_dir)
            # a templated re-read is required (not the raw dict): the
            # template restores typed structure — opt_state NamedTuples
            # come back as plain dicts on the untemplated path
            legacy_like = {k: v for k, v in ckpt_like.items()
                           if k != "rng_impl"}
            return ckpt_mgr.restore(like=legacy_like)

    @contextlib.contextmanager
    def _recompile_scope(self):
        """With ``debug_recompiles``, run the fit under
        :func:`~sparkflow_tpu.analysis.runtime_guards.track_recompiles` and
        keep the tracker's report/findings on the trainer afterwards."""
        if not self.debug_recompiles:
            yield
            return
        from .analysis.runtime_guards import track_recompiles
        with track_recompiles() as tracker:
            try:
                yield
            finally:
                self.recompile_report = tracker.report()
                self.recompile_findings = tracker.findings()

    def fit(self, features, labels: Optional[np.ndarray] = None,
            init_params=None, *, trace_spans=False,
            trace_dir: Optional[str] = None) -> TrainResult:
        """Train. With ``trace_spans`` truthy, the fit runs instrumented:
        per-step phase spans (transfer / compile / steady step / metrics /
        checkpoint) are collected on a fresh :class:`~sparkflow_tpu.obs.Tracer`
        and exported as Chrome-trace JSON + span JSONL (``trace_spans`` may
        be the output path; otherwise one is derived from ``trace_dir``,
        ``checkpoint_dir``, or the system temp dir — see
        ``self.last_trace_path``). Phase totals and throughput/MFU gauges
        land in ``self.last_step_stats`` and the metrics registry. Tracing
        forces the per-epoch loop path (the fused multi-epoch program has
        no host-visible step boundaries to time).

        Whether or not ``trace_spans`` is set, and on the fused path too,
        every fit records its host phases as spans on the active tracer and
        as annotations in a running JAX profile: the root ``train/fit`` and
        under it, disjoint and in this order, ``train/plan``,
        ``train/init_state``, ``train/transfer``, ``train/launch``,
        ``train/wait``, ``train/finish`` (docs/observability.md).

        ``train/init_state`` holds one device program however many leaves
        the model has (:meth:`_fresh_state`: the fit's own copy of
        ``init_params``, which stay the caller's, alive and unchanged, and
        the optimizer's fresh state), after the trainer has let go of the fit
        before's optimizer state. Sharded layouts (tp/fsdp, ZeRO, pp) take
        the one-program copy and build their state as they place it."""
        with self._recompile_scope():
            if not trace_spans:
                return self._fit_impl(features, labels, init_params)
            from .obs import StepStats, Tracer
            tracer = Tracer()
            stats = StepStats(tracer=tracer, metrics=self.metrics)
            self.last_tracer = tracer
            self.last_step_stats = None
            self._tracer, self._step_stats = tracer, stats
            try:
                # activate(): checkpoint/retry spans fired deep in the
                # stack route to this fit's tracer, nested under the root.
                # An ambient trace tracker must exist for the compile-vs-
                # steady probe-count delta; reuse the debug_recompiles one
                # when present (probes record to the innermost tracker
                # only — pushing a second would starve the user's report)
                from .analysis.runtime_guards import (_current_tracker,
                                                      track_recompiles)
                with contextlib.ExitStack() as es:
                    if _current_tracker() is None:
                        es.enter_context(track_recompiles(warn_after=10**9))
                    es.enter_context(tracer.activate())
                    result = self._fit_impl(features, labels, init_params)
            finally:
                self._tracer = None
                self._step_stats = None
            self.last_step_stats = stats._summary
            if isinstance(trace_spans, str):
                path = trace_spans
            else:
                base = trace_dir or self.checkpoint_dir or tempfile.gettempdir()
                path = os.path.join(
                    base, f"sparkflow_tpu_trace_{os.getpid()}.json")
            self.last_trace_path = tracer.export_chrome_trace(path)
            tracer.export_jsonl(
                (path[:-5] if path.endswith(".json") else path) + ".jsonl")
            return result

    def _fit_impl(self, features, labels: Optional[np.ndarray] = None,
                  init_params=None) -> TrainResult:
        from .obs import phases
        with phases("train/fit", jax_annotation=True) as ph:
            return self._fit_phases(ph, features, labels, init_params)

    def _fit_phases(self, ph, features, labels, init_params) -> TrainResult:
        """The fit itself; ``ph`` opens each host phase's span as it begins
        (the names are in :meth:`fit`'s docstring)."""
        ph.enter("train/plan")
        # multi-input features travel as a TUPLE of arrays; a plain list is
        # row data (np.asarray coercible), exactly as in single-input fits
        multi = isinstance(features, tuple)
        n_inputs = (len(self.input_name)
                    if isinstance(self.input_name, (list, tuple)) else 1)
        if multi != (n_inputs > 1) or (multi and len(features) != n_inputs):
            got = (f"a tuple of {len(features)} arrays" if multi
                   else "a single features array")
            raise ValueError(
                f"model takes {n_inputs} input tensor(s) "
                f"({self.input_name}) but fit() got {got}")
        if multi:
            features = tuple(np.ascontiguousarray(f, dtype=np.float32)
                             for f in features)
            n = features[0].shape[0]
            if any(f.shape[0] != n for f in features):
                raise ValueError("multi-input feature arrays disagree on rows")
        else:
            features = np.ascontiguousarray(features, dtype=np.float32)
            n = features.shape[0]
        if n == 0:
            raise ValueError("no training data")
        if labels is not None:
            labels = np.ascontiguousarray(labels, dtype=np.float32)
            if labels.ndim == 1:
                labels = labels[:, None]

        if self.strategy == "elastic_dp":
            return self._fit_elastic(features, labels, init_params,
                                     multi=multi)

        strategy = self._mesh_strategy()
        task = self._strategy_task(strategy) if strategy != "default" else None
        if strategy != "default":
            # pp/sp steps have no padded-row masking: every batch must be
            # all-real rows. Trim the dataset to whole batches (stochastic
            # batches sample real rows only, so just the dp-rounding must
            # fit inside n).
            dp = self._dp_size()
            bs = self.mini_batch_size
            stoch = bool(self.mini_stochastic_iters
                         and self.mini_stochastic_iters > 0)
            if bs is None or bs <= 0 or bs >= n:
                unit = dp
            elif stoch:
                unit = dp
            else:
                unit = -(-bs // dp) * dp  # the planned sweep batch
            n_use = (n // unit) * unit
            if n_use == 0:
                raise ValueError(
                    f"dataset of {n} rows is smaller than one {strategy} "
                    f"batch ({unit} rows)")
            if n_use != n:
                logger.warning(
                    "%s fit drops the %d-row remainder (pp/sp steps carry "
                    "no padded-row masking); a miniBatchSize dividing %d "
                    "trains on every row", strategy, n - n_use, n)
                n = n_use
                features = (tuple(f[:n] for f in features) if multi
                            else features[:n])
                if labels is not None:
                    labels = labels[:n]

        mode, batch, num_batches = self._plan(n)
        if strategy != "default" and batch > n:
            raise ValueError(
                f"mini_batch_size rounds to {batch} rows (> dataset {n}); "
                f"{strategy} fits cannot pad batches — lower miniBatchSize")
        # the padded dataset always covers exactly ceil(n/batch) windows; in
        # stochastic mode num_batches may exceed that (resampled permutations)
        total = -(-n // batch) * batch
        # strategy steps have NO padded-row masking: sweep/full epochs must
        # be pad-free after the trim (stochastic mode is exempt — its
        # batches sample indices from the n REAL rows only, so the padded
        # tail is never read). Guards the trim-unit/_plan rounding coupling:
        # if they ever diverge, fail here instead of training on padding.
        if strategy != "default" and mode != "stochastic" and total != n:
            raise RuntimeError(
                f"{strategy} fit planned {total} padded rows over {n} real "
                f"ones — internal trim/_plan divergence, please report")
        if multi:
            padded = [pad_to_batches(f, batch, total // batch)
                      for f in features]
            x_pad, mask = tuple(p for p, _ in padded), padded[0][1]
        else:
            x_pad, mask = pad_to_batches(features, batch, total // batch)
        if labels is not None:
            y_pad, _ = pad_to_batches(labels, batch, total // batch)
        elif task == "lm" and not multi:
            # unsupervised pp-lm/sp fits consume the label slot as the
            # attention mask (token loss weights); single-input means no
            # mask column -> every token weighs 1
            y_pad = np.ones((total, features.shape[1]), np.float32)
        else:
            y_pad = np.zeros((total, 1), np.float32)  # dummy; loss ignores it

        ph.enter("train/init_state")
        # the last fit's optimizer state goes before the new one is made: two
        # fits' states never stand on the device together (ema_weights() is
        # None from here until this fit ends)
        self._last_opt_state = None
        rng = self._make_rng()
        init_rng, rng = jax.random.split(rng)
        # the caller's arrays until _fresh_state below has made this fit's
        # own copy of them (the epoch program donates its params buffers,
        # which would delete the caller's arrays)
        params = (init_params if init_params is not None
                  else self.model.init(init_rng))
        if strategy == "pp":
            # repack into the stage-stacked pipeline layout, sharded over
            # 'pp' (merged back to the standard layout at the end of fit,
            # so serving/weights export never see pipeline internals)
            from .parallel.pp import pp_pspecs, split_stage_params
            params = split_stage_params(
                self.model, self._fresh_state(params, with_opt=False),
                self.mesh.shape["pp"])
            pspecs = pp_pspecs(params)
        else:
            pspecs = self._resolve_pspecs()
        self._zero_stage = self._resolve_zero_stage(strategy, pspecs, params)
        self._zero1_active = self._zero_stage >= 1
        self._zero3_template = None
        self._offload_active = bool(self.sharding is not None
                                    and self.sharding.offload_opt_state
                                    and self.mesh is not None)
        opt_state = None
        if pspecs is not None:
            # tp/fsdp/pp: place params per their PartitionSpecs BEFORE the
            # optimizer init so mu/nu/etc inherit the same placement. That
            # init stays eager: under jit a zeros_like of a sharded parameter
            # has no data dependence on it and may come out replicated
            if strategy != "pp":
                params = self._fresh_state(params, with_opt=False)
            params = self._place_params(params, pspecs)
        elif self._zero1_active:
            # ZeRO builds its state below in its own flat layout, from
            # params replicated on the mesh
            params = self._fresh_state(params, with_opt=False, replicate=True)
        else:
            # default, and pure dp/sp: params and state from one program.
            # With a mesh both come out replicated on it from the start, as
            # every step's output is: an argument's type carries its mesh,
            # and state left unplaced made the second call of the same
            # program trace (and on a chip compile) again with identical
            # shapes
            params, opt_state = self._fresh_state(
                params, with_opt=True, replicate=self.mesh is not None)
        opt_shardings = None
        param_shardings = None
        if self._zero1_active:
            # ZeRO: the state is built in the flat [dp, s]-leaf layout and
            # physically sharded over dp; the epoch program pins that
            # placement (opt_shardings) so donation round-trips keep it.
            # The layout is IDENTICAL for stages 1-3 (init over flat params
            # == init over standard params), so checkpoints interchange.
            from .optimizers_sharded import (place_zero1_state, sharded_update,
                                             zero1_state_shardings)
            dp_n = self._dp_size()
            dp_ax = self._data_axis()
            wrapped = sharded_update(self.optimizer, dp_n, dp_ax)
            opt_state = place_zero1_state(wrapped.init(params), self.mesh,
                                          dp_n, dp_ax)
            opt_shardings = zero1_state_shardings(opt_state, self.mesh, dp_n,
                                                  dp_ax)
            if self._zero_stage >= 3:
                # ZeRO-3: params live at rest in the flat [dp, s] layout,
                # row-sharded like the opt state; the standard-shape
                # template drives the JIT gather and checkpoint conversion
                from .optimizers_sharded import (shard_zero3_params,
                                                 zero3_param_shardings)
                self._zero3_template = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
                params = shard_zero3_params(params, dp_n)
                param_shardings = zero3_param_shardings(params, self.mesh,
                                                        dp_n, dp_ax)
                params = jax.tree.map(jax.device_put, params, param_shardings)
        elif opt_state is None:
            opt_state = self.optimizer.init(params)

        ckpt_mgr = None
        start_epoch = 0
        ckpt_like = None
        if self.checkpoint_dir:
            from .checkpoint import CheckpointManager
            ckpt_mgr = CheckpointManager(self.checkpoint_dir)
            # host-side structural template, captured BEFORE any donation can
            # invalidate device buffers (restore-after-failure needs it)
            std_p = self._params_to_ckpt(params)
            ckpt_like = jax.tree.map(
                np.asarray, _ckpt_state(std_p,
                                        self._opt_to_ckpt(std_p, opt_state),
                                        0, rng, rng_impl=self.rng_impl))
            state = self._ckpt_restore(ckpt_mgr, ckpt_like)
            if state is not None:
                params = jax.tree.map(jnp.asarray, state["params"])
                opt_state = self._opt_from_ckpt(
                    params, jax.tree.map(jnp.asarray, state["opt_state"]))
                if pspecs is not None:
                    # restored arrays are host-loaded; re-place params (the
                    # opt state re-places lazily via inferred shardings on
                    # the first compiled step after resume)
                    params = self._place_params(params, pspecs)
                # checkpoints hold the STANDARD layout; stage 3 re-flattens
                # and re-shards for THIS mesh's dp size
                params = self._params_from_ckpt(params)
                start_epoch = int(state["epoch"])
                rng = self._restore_rng(state["rng"], state.get("rng_impl"))
                logger.info("resumed from checkpoint at epoch %d", start_epoch)

        # Stage the dataset on device(s) once; every epoch runs fully on-device.
        stats = self._step_stats  # set by fit(trace_spans=...), else None
        if stats is not None:
            # everything up to here (validation, plan, init, restore) is
            # one-time setup; charging it keeps phase sums ≈ wall time
            stats.add("setup", stats.elapsed_s())
        ph.enter("train/transfer")
        t_stage = time.perf_counter()
        device_args = (jax.tree.map(jnp.asarray, x_pad),
                       jnp.asarray(y_pad), jnp.asarray(mask))
        if stats is not None:
            # sync inside the phase so host->device transfer is charged
            # here and not to the first step
            jax.block_until_ready(device_args)
            stats.add("transfer", time.perf_counter() - t_stage)
        ph.leave()

        loss_by_it = {}  # device scalars; converted lazily to keep async dispatch
        t0 = time.perf_counter()
        it = 0
        ran = 0
        total_epochs = self.partition_shuffles * self.iters
        retries_left = self.resume_retries if ckpt_mgr is not None else 0
        epoch_secs = []  # straggler heartbeat history (opt-in)

        # FAST PATH: nothing host-side needs per-epoch control -> run every
        # remaining epoch as ONE compiled program (lax.scan over the epoch
        # body; single device dispatch for the whole fit). Per-epoch rngs are
        # generated exactly like the loop below, so losses match it.
        if strategy != "default":
            step_fn = self._make_strategy_step(strategy, task, batch)
        elif self._zero1_active:
            step_fn = self._make_zero_step(
                param_template=self._zero3_template)
        else:
            step_fn = None
        k = total_epochs - start_epoch
        # span tracing joins the needs-per-epoch-host-control set: the fused
        # program is one opaque dispatch with no step boundaries to time
        # (and opt-state offload needs the per-epoch call boundary to
        # refresh its host mirror)
        if (k > 1 and not self.verbose and self.loss_callback is None
                and ckpt_mgr is None and not self.straggler_factor
                and not self.halt_on_nan and stats is None
                and not self._offload_active
                and not (self._publish_store is not None
                         and self.publish_every > 0)):
            fkey = ("fused", batch, num_batches, mode, self.shuffle_per_iter,
                    n if mode == "stochastic" else None, k,
                    pspecs is not None, strategy,
                    self.pp_schedule, self.pp_microbatches,
                    self._zero_stage)
            if fkey not in self._epoch_cache:
                loss_fn = make_loss_fn(self.model, self.input_name,
                                       self.label_name, with_metrics=True)
                self._epoch_cache[fkey] = make_multi_epoch_fn(
                    loss_fn, self.optimizer, batch, num_batches, mode,
                    self.shuffle_per_iter, k, self.mesh, n_real=n,
                    infer_params=pspecs is not None, step_fn=step_fn,
                    opt_shardings=opt_shardings,
                    param_shardings=param_shardings,
                    sharding=self.sharding)
            ph.enter("train/launch")
            erngs = []
            for _ in range(k):
                rng, erng = jax.random.split(rng)
                erngs.append(erng)
            params, opt_state, losses = self._epoch_cache[fkey](
                params, opt_state, *device_args, jnp.stack(erngs))
            ph.enter("train/wait")
            params = jax.block_until_ready(params)
            ph.enter("train/finish")
            wall = time.perf_counter() - t0
            per_epoch = num_batches * batch if mode == "stochastic" else n
            if strategy == "pp":
                from .parallel.pp import merge_stage_params
                params = merge_stage_params(self.model, params)
            params = self._params_to_ckpt(params)
            self.params = params
            self._last_opt_state = opt_state
            metrics = None
            if isinstance(losses, tuple):    # a model with counters
                losses, metrics = losses
                metrics = jax.device_get(metrics)
            epoch_losses = [float(l) for l in jnp.mean(losses, axis=1)]
            self._warn_non_finite(epoch_losses)
            if self._publish_store is not None:
                self._publish_weights(params)
            return TrainResult(params, epoch_losses,
                               per_epoch * k / max(wall, 1e-9), wall,
                               metrics=metrics)

        cache_key = (batch, num_batches, mode, self.shuffle_per_iter,
                     n if mode == "stochastic" else None, pspecs is not None,
                     strategy, self.pp_schedule, self.pp_microbatches,
                     self._zero_stage)
        if cache_key not in self._epoch_cache:
            loss_fn = make_loss_fn(self.model, self.input_name, self.label_name)
            self._epoch_cache[cache_key] = make_epoch_fn(
                loss_fn, self.optimizer, batch, num_batches, mode,
                self.shuffle_per_iter, self.mesh, n_real=n,
                infer_params=pspecs is not None, step_fn=step_fn,
                opt_shardings=opt_shardings,
                param_shardings=param_shardings, sharding=self.sharding)
        epoch_fn = self._epoch_cache[cache_key]
        if self._offload_active:
            epoch_fn = self._wrap_offload(epoch_fn, opt_shardings)

        if stats is not None:
            # compile-vs-steady detection: the core trace probes record
            # every XLA trace on the ambient tracker (fit(trace_spans=...)
            # guarantees one is active); a probe-count delta across the
            # epoch call means that call paid a compile
            from .analysis.runtime_guards import _current_tracker
            stats.examples_per_step = (num_batches * batch
                                       if mode == "stochastic" else n)

            def _probe_count() -> int:
                tr = _current_tracker()
                return sum(tr.traces.values()) if tr is not None else 0

        from .utils.preempt import NullGuard, PreemptionGuard
        guard = PreemptionGuard() if ckpt_mgr is not None else NullGuard()
        preempted = False
        nan_halted = False
        with guard:
          while True:
            try:
                it = 0
                for _round in range(self.partition_shuffles):
                    for _epoch in range(self.iters):
                        if guard.requested:
                            # preemption (SIGTERM): save and stop cleanly;
                            # the next fit on this checkpoint_dir resumes
                            # here. max(it, start_epoch): during the resume
                            # skip phase `it` is behind the restored state —
                            # labeling below start_epoch would regress the
                            # checkpoint
                            at = max(it, start_epoch)
                            std_p = self._params_to_ckpt(params)
                            ckpt_mgr.save(
                                at, _ckpt_state(std_p,
                                                self._opt_to_ckpt(std_p, opt_state),
                                                at, rng, rng_impl=self.rng_impl))
                            logger.warning(
                                "preempted: checkpoint saved at epoch %d", at)
                            preempted = True
                            break
                        it += 1
                        if it <= start_epoch:
                            # the restored rng was saved AFTER these epochs'
                            # splits — skip without touching it so the stream
                            # continues exactly where the interrupted run
                            # left off
                            continue
                        te = time.perf_counter()
                        ph.enter("train/launch")
                        rng, erng = jax.random.split(rng)
                        if stats is None:
                            params, opt_state, losses = epoch_fn(
                                params, opt_state, *device_args, erng)
                            ph.leave()
                        else:
                            stats.begin_step()
                            probes_before = _probe_count()
                            ts0 = time.perf_counter()
                            params, opt_state, losses = epoch_fn(
                                params, opt_state, *device_args, erng)
                            ph.leave()
                            # sync so the step phase owns its real device
                            # time (async dispatch would smear it into the
                            # metrics/checkpoint phases)
                            jax.block_until_ready((params, losses))
                            ts1 = time.perf_counter()
                            step_compiled = _probe_count() > probes_before
                            pname = ("step_compile" if step_compiled
                                     else "step")
                            stats.add(pname, ts1 - ts0)
                            self._tracer.record(
                                f"train/{pname}", ts0, ts1,
                                parent=self._tracer.current(),
                                args={"epoch": it})
                        loss_by_it[it] = jnp.mean(losses)
                        ran += 1
                        needs_loss_val = (self.halt_on_nan or self.verbose
                                          or self.loss_callback is not None)
                        with (stats.phase("metrics") if stats is not None
                              else contextlib.nullcontext()):
                            loss_val = (float(loss_by_it[it])  # ONE device sync
                                        if needs_loss_val else None)
                            if self.halt_on_nan and not np.isfinite(loss_val):
                                logger.error(
                                    "non-finite loss %r at epoch %d: halting "
                                    "(halt_on_nan=True); check the learning "
                                    "rate / input data, or resume from the "
                                    "last finite checkpoint", loss_val, it)
                                nan_halted = True
                                preempted = True  # reuse the clean-stop path
                                break
                            if self.verbose or self.loss_callback is not None:
                                if self.verbose:
                                    logger.info("iteration %d loss %f", it,
                                                loss_val)
                                self.metrics.scalar("train/loss", loss_val,
                                                    step=it)
                                if self.loss_callback is not None:
                                    # reference signature: loss_callback(loss,
                                    # iteration, partition_id) —
                                    # HogwildSparkModel.py:99-100; one logical
                                    # partition here.
                                    self.loss_callback(loss_val, it, 0)
                        if self.straggler_factor:
                            jax.block_until_ready(loss_by_it[it])
                            secs = time.perf_counter() - te
                            if len(epoch_secs) >= 3:
                                med = float(np.median(epoch_secs))
                                if secs > self.straggler_factor * med:
                                    logger.warning(
                                        "straggling epoch %d: %.3fs vs "
                                        "median %.3fs", it, secs, med)
                                    self.metrics.scalar("train/straggler_secs",
                                                        secs, step=it)
                                    if self.straggler_callback is not None:
                                        self.straggler_callback(it, secs, med)
                            epoch_secs.append(secs)
                        if (ckpt_mgr is not None and self.checkpoint_every > 0
                                and (it % self.checkpoint_every == 0
                                     or it == total_epochs)):
                            with (stats.phase("checkpoint")
                                  if stats is not None
                                  else contextlib.nullcontext()):
                                std_p = self._params_to_ckpt(params)
                                ckpt_mgr.save(
                                    it, _ckpt_state(
                                        std_p,
                                        self._opt_to_ckpt(std_p, opt_state),
                                        it, rng, rng_impl=self.rng_impl))
                        if (self._publish_store is not None
                                and self.publish_every > 0
                                and (it % self.publish_every == 0
                                     or it == total_epochs)):
                            self._publish_weights(self._params_to_ckpt(params))
                        if stats is not None:
                            stats.end_step(compiled=step_compiled)
                    if preempted:
                        break
                break
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                # pod-scale failure handling: restore the last checkpoint and
                # keep training (the reference dropped the update and printed,
                # HogwildSparkModel.py:68-92 — unacceptable per SURVEY.md §5)
                state = (self._ckpt_restore(ckpt_mgr, ckpt_like)
                         if retries_left > 0 else None)
                if state is None:
                    raise
                retries_left -= 1
                params = jax.tree.map(jnp.asarray, state["params"])
                opt_state = self._opt_from_ckpt(
                    params, jax.tree.map(jnp.asarray, state["opt_state"]))
                params = self._params_from_ckpt(params)
                start_epoch = int(state["epoch"])
                rng = self._restore_rng(state["rng"], state.get("rng_impl"))
                # epochs past the restore point will re-run: drop their losses
                loss_by_it = {k: v for k, v in loss_by_it.items()
                              if k <= start_epoch}
                logger.warning(
                    "training failure at iteration %d (%s: %s); auto-resumed "
                    "from checkpoint epoch %d (%d retries left)", it,
                    type(e).__name__, e, start_epoch, retries_left)
        # block until the last step is done for honest timing
        ph.enter("train/wait")
        params = jax.block_until_ready(params)
        ph.enter("train/finish")
        wall = time.perf_counter() - t0
        if stats is not None:
            # FLOPs per "step" (= one epoch_fn call = num_batches optimizer
            # steps) via XLA cost analysis; best-effort — it compiles a
            # probe step (clock stopped first so that compile doesn't
            # inflate the fit's wall time), and some strategies/backends
            # can't price it
            stats.stop_clock()
            flops = None
            if not multi:
                try:
                    from .utils.flops import train_step_flops
                    per_batch = train_step_flops(
                        self.model, self.input_name, self.label_name,
                        self.optimizer, x_pad[:batch], y_pad[:batch])
                    if per_batch:
                        flops = per_batch * num_batches
                except Exception:
                    flops = None
            stats.finalize(flops_per_step=flops)
        # real examples per epoch: padded rows carry zero weight and don't
        # count; stochastic mode counts sampled slots (its actual step volume)
        per_epoch = num_batches * batch if mode == "stochastic" else n
        seen = per_epoch * ran
        if strategy == "pp":
            from .parallel.pp import merge_stage_params
            params = merge_stage_params(self.model, params)
        params = self._params_to_ckpt(params)
        self.params = params
        self._last_opt_state = self._flush_opt_state(opt_state)
        epoch_keys = sorted(loss_by_it)
        epoch_losses = [float(loss_by_it[k]) for k in epoch_keys]
        if not nan_halted:  # the halt already logged its own ERROR
            self._warn_non_finite(epoch_losses, epoch_keys)
        stop = ("nan" if nan_halted
                else "preempted" if preempted else "completed")
        # publish-at-end mode (publish_every == 0): the fit's final weights
        # become the next served version — but never NaN-halted ones
        if (self._publish_store is not None and self.publish_every <= 0
                and not nan_halted):
            self._publish_weights(params)
        return TrainResult(params, epoch_losses, seen / max(wall, 1e-9), wall,
                           stop_reason=stop)

    def _publish_weights(self, std_params) -> None:
        """Best-effort push of standard-layout weights to the configured
        :class:`~sparkflow_tpu.serving.weightstore.WeightStore`. A failed
        publication logs and moves on — it must never fail training, and
        serving replicas keep last-good weights regardless."""
        try:
            v = self._publish_store.publish(std_params)
            logger.info("trainer: published weights as version %d", v)
        except Exception:
            logger.exception("trainer: live weight publication failed")

    def _fit_elastic(self, features, labels, init_params,
                     multi: bool) -> TrainResult:
        """strategy='elastic_dp': train through
        :class:`~sparkflow_tpu.parallel.elastic.ElasticDPEngine` — N replica
        threads over round-robin data shards, exchanging gradients through
        the bounded-staleness versioned store instead of a sync all-reduce.
        Reference semantics preserved: per-replica batch is miniBatchSize
        and each replica makes ``iters`` passes over its shard per shuffle
        round, like the reference's per-partition workers."""
        if multi:
            raise ValueError(
                "strategy='elastic_dp' supports single-input models only "
                "(multi-input gradient exchange is not implemented); use "
                "the sync path")
        if self.checkpoint_dir:
            logger.warning(
                "strategy='elastic_dp' ignores checkpoint_dir: the async "
                "store has no epoch boundary to checkpoint at (resume "
                "support is a sync-path feature)")

        from .core import make_loss_fn
        from .parallel.elastic import ElasticDPEngine

        n = features.shape[0]
        replicas = int(self.elastic.get("replicas", 4))
        if replicas < 1:
            raise ValueError(f"elastic replicas must be >= 1, got {replicas}")
        replicas = min(replicas, n)  # every replica needs at least one row

        rng = self._make_rng()
        init_rng, _rng = jax.random.split(rng)
        if init_params is not None:
            params = self._fresh_state(init_params, with_opt=False)
        else:
            params = self.model.init(init_rng)

        # engine calls back as (loss, replica_step, replica_index) — the
        # same shape as the sync path's (loss, iteration, partition_id)
        engine = ElasticDPEngine(
            make_loss_fn(self.model, self.input_name, self.label_name),
            self.optimizer, params,
            max_staleness=int(self.elastic.get("max_staleness", 4)),
            dampening=self.elastic.get("dampening", "inverse"),
            density_threshold=self.elastic.get("density_threshold", 0.25),
            lease_ttl_s=float(self.elastic.get("lease_ttl_s", 10.0)),
            metrics=self.metrics, loss_callback=self.loss_callback,
            publish_to=self._publish_store, publish_every=self.publish_every)

        shards = [(features[i::replicas],
                   labels[i::replicas] if labels is not None else None)
                  for i in range(replicas)]
        # mini_batch_size <= 0 means full-batch (the sync planner's 'full'
        # mode); per replica that is its whole shard per step
        bs = self.mini_batch_size
        if bs is None or bs <= 0:
            bs = n
        epochs = max(1, self.iters) * self.partition_shuffles
        result = engine.run_threads(
            shards, epochs=epochs, batch_size=bs, seed=self.seed)

        self.params = result.params
        self._last_opt_state = result.opt_state
        self.last_elastic_stats = result.stats
        if self.verbose:
            logger.info(
                "elastic fit: %d replicas, %d accepted / %d rejected-stale "
                "/ %d dropped pushes, final version %d",
                replicas, result.stats["accepted"],
                result.stats["rejected_stale"],
                result.stats["dropped_stale"] + result.stats["dropped_fault"],
                result.version)
        if self._publish_store is not None and self.publish_every <= 0:
            self._publish_weights(result.params)
        return TrainResult(result.params, result.losses,
                           result.examples_per_sec, result.wall_s,
                           stop_reason="completed")

    def ema_weights(self):
        """The debiased Polyak-averaged weight tree from the last fit, when
        the optimizer was built with the ``ema_decay`` config key; None
        otherwise. Serve these instead of the raw final weights for the
        usual EMA quality bump. A fit lets go of the fit before's optimizer
        state as it begins (two states never stand on the device together),
        so during a fit, and after one that raised, this is None and not the
        earlier fit's average."""
        if self._last_opt_state is None:
            return None
        from .optimizers import extract_ema_params
        state = self._last_opt_state
        if self._zero1_active and self.params is not None:
            # defensive: zero1 'auto' declines when ema_decay is configured,
            # but a hand-built optax chain can slip past the config gate —
            # EMA leaves then live in the flat [dp, s] layout and need the
            # standard-form conversion before extraction
            state = self._opt_to_ckpt(self.params, state)
        ema = extract_ema_params(state)
        if ema is not None and self.mesh is not None \
                and self._mesh_strategy() == "pp":
            # the pp opt state tracks the stage-stacked layout; serve the
            # standard layout like fit() does for the final weights
            from .parallel.pp import merge_stage_params
            ema = merge_stage_params(self.model, ema)
        return ema

    @staticmethod
    def _warn_non_finite(epoch_losses, epoch_numbers=None):
        """Post-hoc divergence warning. ``epoch_numbers`` labels each loss
        with its REAL epoch (a resumed run's list starts mid-stream; list
        positions would mislabel the divergence point)."""
        nums = epoch_numbers or list(range(1, len(epoch_losses) + 1))
        bad = [n for n, l in zip(nums, epoch_losses) if not np.isfinite(l)]
        if bad:
            logger.warning(
                "training diverged: non-finite loss at epoch(s) %s (of %d "
                "epochs run) — the returned weights are NaN-contaminated; "
                "lower the learning rate or enable halt_on_nan",
                bad[:5], len(epoch_losses))

    def fit_stream(self, row_iterator, init_params=None, queue_capacity: int = 8,
                   chunk: int = 1024, epochs: int = 1) -> TrainResult:
        with self._recompile_scope():
            return self._fit_stream_impl(row_iterator, init_params,
                                         queue_capacity, chunk, epochs)

    def _fit_stream_impl(self, row_iterator, init_params=None,
                         queue_capacity: int = 8, chunk: int = 1024,
                         epochs: int = 1) -> TrainResult:
        """Streaming fit for datasets that don't fit in device memory.

        ``row_iterator`` yields ``(features, label)`` pairs (bare features when
        unsupervised), or is a zero-arg callable returning a fresh such
        iterator (required when ``epochs > 1`` — streams are single-pass, so
        each epoch re-pulls the source, matching Spark's ``rdd.toLocalIterator``
        semantics). Optimizer state, the rng stream, and the loss history
        carry across epochs — multiple epochs here train identically to
        repeated passes over an in-memory dataset, not like restarted fits.

        Multi-input models (``input_name`` a sequence) stream too: each row's
        features travel as a TUPLE of vectors, ride the batch ring
        concatenated into one flat row, and are split back into per-input
        arrays before the train step.

        A native C++ batch-assembly thread (numpy fallback) pads/masks/
        shuffles fixed-shape batches concurrently with device compute; each
        batch is one synchronous optimizer step.
        """
        import itertools as _it

        from .core import make_train_step
        from .localml.linalg import vector_to_array
        from .utils.data import BatchQueue, feed_from_iterator

        if self._mesh_strategy() != "default":
            raise ValueError(
                "fit_stream trains dp/tp/fsdp/ep meshes; pp/sp strategy "
                "fits need the whole dataset staged for their fixed-shape "
                "batch schedules — use fit() (fitMode='collect')")
        multi = isinstance(self.input_name, (list, tuple))
        factory = row_iterator if callable(row_iterator) else None
        if epochs > 1 and factory is None:
            raise ValueError("epochs > 1 needs a callable iterator factory "
                             "(streams are single-pass)")

        supervised = self.label_name is not None
        rng = self._make_rng()
        init_rng, rng = jax.random.split(rng)

        bs = self.mini_batch_size if self.mini_batch_size and self.mini_batch_size > 0 else 128
        bs = -(-bs // self._dp_size()) * self._dp_size()

        if init_params is not None:
            # copy: the train step donates its params buffers
            params = self._fresh_state(init_params, with_opt=False)
        else:
            params = self.model.init(init_rng)
        pspecs = self._resolve_pspecs()
        if pspecs is not None:
            # streaming honors tp/fsdp sharding exactly like fit(): place
            # params first so the optimizer state inherits the placement
            params = self._place_params(params, pspecs)
        self._zero_stage = self._resolve_zero_stage("default", pspecs, params)
        self._zero1_active = self._zero_stage >= 1
        self._zero3_template = None
        self._offload_active = bool(self.sharding is not None
                                    and self.sharding.offload_opt_state
                                    and self.mesh is not None)
        opt_shardings = None
        if self._zero1_active:
            # same zero wiring as fit(): sharded state, reduce_scatter step
            # (make_dp_train_step has make_train_step's signature)
            from .optimizers_sharded import (place_zero1_state, sharded_update,
                                             zero1_state_shardings)
            from .parallel.dp import make_dp_train_step
            dp_n = self._dp_size()
            dp_ax = self._data_axis()
            wrapped = sharded_update(self.optimizer, dp_n, dp_ax)
            opt_state = place_zero1_state(wrapped.init(params), self.mesh,
                                          dp_n, dp_ax)
            opt_shardings = zero1_state_shardings(opt_state, self.mesh, dp_n,
                                                  dp_ax)
            if self._zero_stage >= 3:
                from .optimizers_sharded import (shard_zero3_params,
                                                 zero3_param_shardings)
                self._zero3_template = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
                params = shard_zero3_params(params, dp_n)
                params = jax.tree.map(
                    jax.device_put, params,
                    zero3_param_shardings(params, self.mesh, dp_n, dp_ax))
            step = make_dp_train_step(
                self.model, self.optimizer, self.mesh, self.input_name,
                self.label_name, sharding=self._active_cfg(),
                param_template=self._zero3_template)
        else:
            opt_state = self.optimizer.init(params)
            loss_fn = make_loss_fn(self.model, self.input_name,
                                   self.label_name)
            step = make_train_step(loss_fn, self.optimizer, self.mesh,
                                   infer_params=pspecs is not None,
                                   sharding=self.sharding)
        if self._offload_active:
            # streaming: per-step double-buffered offload — the host mirror
            # refreshes behind each step's compute instead of a synchronous
            # hop around every step
            step = self._wrap_offload(step, opt_shardings)

        ckpt_mgr = None
        start_step = 0
        if self.checkpoint_dir:
            # streaming checkpoint/resume: state is saved every
            # checkpoint_every STEPS; a restart restores weights + optimizer
            # state and continues on the incoming stream (streams can't
            # rewind, so previously consumed rows are not replayed)
            from .checkpoint import CheckpointManager
            ckpt_mgr = CheckpointManager(self.checkpoint_dir)
            std_p = self._params_to_ckpt(params)
            like = jax.tree.map(
                np.asarray, _ckpt_state(std_p,
                                        self._opt_to_ckpt(std_p, opt_state),
                                        0, rng, rng_impl=self.rng_impl))
            state = self._ckpt_restore(ckpt_mgr, like)
            if state is not None:
                params = jax.tree.map(jnp.asarray, state["params"])
                opt_state = self._opt_from_ckpt(
                    params, jax.tree.map(jnp.asarray, state["opt_state"]))
                if pspecs is not None:
                    params = self._place_params(params, pspecs)
                params = self._params_from_ckpt(params)
                start_step = int(state["epoch"])
                rng = self._restore_rng(state["rng"], state.get("rng_impl"))
                logger.info("fit_stream resumed weights from step %d",
                            start_step)

        losses = []
        seen = 0
        nan_halted = False
        it_count = start_step
        t0 = time.perf_counter()
        dummy_y = np.zeros((bs, 1), np.float32)
        from .utils.preempt import NullGuard, PreemptionGuard
        stream_guard = (PreemptionGuard() if ckpt_mgr is not None
                        else NullGuard())
        preempt_saved = False
        with stream_guard:
            for epoch in range(max(1, epochs)):
                if stream_guard.requested:
                    # signal landed between epochs (feeder teardown /
                    # iterator setup window): persist before stopping, same
                    # contract as the in-loop check
                    if ckpt_mgr is not None and not preempt_saved:
                        ckpt_mgr.save(it_count, _ckpt_state(
                            self._params_to_ckpt(params),
                            self._opt_to_ckpt(params, opt_state),
                            it_count, rng, rng_impl=self.rng_impl))
                        logger.warning("preempted: checkpoint saved at "
                                       "stream step %d", it_count)
                    break
                it = iter(factory() if factory else row_iterator)
                try:
                    first = next(it)
                except StopIteration:
                    raise ValueError("no training data")
                raw0 = first[0] if supervised else first
                if multi:
                    if (not isinstance(raw0, tuple)
                            or len(raw0) != len(self.input_name)):
                        got = (f"a tuple of {len(raw0)}"
                               if isinstance(raw0, tuple) else "a single vector")
                        raise ValueError(
                            f"model takes {len(self.input_name)} input "
                            f"tensors ({self.input_name}) but the stream "
                            f"yields {got} per row")
                    part_dims = [int(vector_to_array(p).shape[0])
                                 for p in raw0]
                    split_at = list(np.cumsum(part_dims))[:-1]
                    row_dim = int(sum(part_dims))
                else:
                    row_dim = int(vector_to_array(raw0).shape[0])
                if supervised:
                    lbl0 = first[1]
                    label_dim = (1 if isinstance(lbl0, (int, float))
                                 else len(vector_to_array(lbl0)))
                else:
                    label_dim = 0

                q = BatchQueue(bs, row_dim, label_dim, capacity=queue_capacity,
                               shuffle=self.shuffle_per_iter,
                               seed=self.seed + epoch)
                feeder = feed_from_iterator(q, _it.chain([first], it), supervised,
                                            chunk)
                # NOTE on overlap: the step dispatch is async (JAX enqueues the
                # computation and the arg transfers), so the device runs batch N
                # while this loop pops/assembles batch N+1 — an explicit
                # device_put lookahead would only delay step N's dispatch behind
                # the (possibly slow) pop of N+1
                try:
                    for x, y, mask, n_real in q:
                        if stream_guard.requested:
                            # preemption: persist and stop; the stream can't
                            # rewind, so unconsumed rows are not replayed (the
                            # caller's iterator factory re-pulls the source)
                            if ckpt_mgr is not None:
                                ckpt_mgr.save(it_count, _ckpt_state(
                                    self._params_to_ckpt(params),
                                    self._opt_to_ckpt(params, opt_state),
                                    it_count, rng, rng_impl=self.rng_impl))
                                preempt_saved = True
                            logger.warning("preempted: stopping stream at step "
                                           "%d", it_count)
                            # unblock the producer BEFORE feeder.join(): it
                            # may be mid-push into a full queue (close is
                            # idempotent; the finally re-calls it harmlessly)
                            q.close()
                            break
                        rng, srng = jax.random.split(rng)
                        if multi:
                            # split the concatenated ring row back into the
                            # per-input arrays the loss feeds by tensor name
                            x = tuple(np.ascontiguousarray(s) for s in
                                      np.split(x, split_at, axis=1))
                        params, opt_state, loss = step(params, opt_state, x,
                                                       y if supervised else dummy_y,
                                                       mask, srng)
                        losses.append(loss)
                        seen += n_real
                        it_count += 1
                        # opt-in: costs a per-step device sync (the loop is
                        # otherwise fully async), so only when requested
                        if self.halt_on_nan and not np.isfinite(float(loss)):
                            logger.error(
                                "non-finite loss at stream step %d: halting "
                                "(halt_on_nan=True)", it_count)
                            nan_halted = True
                            q.close()
                            break
                        if self.loss_callback is not None:
                            self.loss_callback(float(loss), it_count, 0)
                        if (ckpt_mgr is not None and self.checkpoint_every > 0
                                and it_count % self.checkpoint_every == 0):
                            ckpt_mgr.save(it_count, _ckpt_state(
                                self._params_to_ckpt(params),
                                self._opt_to_ckpt(params, opt_state),
                                it_count, rng, rng_impl=self.rng_impl))
                    feeder.join()
                    if nan_halted:
                        break
                finally:
                    # always tear the queue down (drains and unblocks the feeder);
                    # without this a failing step would leak the native ring and
                    # leave the producer thread blocked forever
                    q.close()
        params = jax.block_until_ready(params)
        wall = time.perf_counter() - t0
        params = self._params_to_ckpt(params)
        self.params = params
        self._last_opt_state = self._flush_opt_state(opt_state)
        step_losses = [float(l) for l in losses]
        if not nan_halted:  # the halt already logged its own ERROR
            self._warn_non_finite(step_losses)
        stop = ("nan" if nan_halted
                else "preempted" if stream_guard.requested else "completed")
        return TrainResult(params, step_losses, seen / max(wall, 1e-9), wall,
                           stop_reason=stop)

    # -- conveniences -------------------------------------------------------

    def weights_list(self) -> List[np.ndarray]:
        """Final weights as a flat array list (reference
        ``tensorflow_get_weights``, ``sparkflow/ml_util.py:9-13``)."""
        if self.params is None:
            raise RuntimeError("fit() has not been run")
        return params_to_list(self.model, self.params)

    def predict_fn(self, output_name: str, dropout_value: float = 1.0,
                   mesh=None) -> Callable:
        """``mesh=`` opts into dp-sharded batch inference (batches of any
        size are padded internally up to a dp multiple); default stays
        single-device. On a trainer whose params carry tp/fsdp placements,
        the program infers those shardings so the placed tree serves in
        place instead of all-gathering."""
        infer = self._resolve_pspecs() is not None and mesh is not None
        return make_predict_fn(self.model, self.input_name, output_name,
                               self.dropout_name, dropout_value, mesh=mesh,
                               infer_params=infer)
