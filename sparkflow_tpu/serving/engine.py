"""AOT-compiled online-inference engine.

The offline path (:func:`sparkflow_tpu.core.make_predict_fn` +
``predict_in_chunks``) relies on ``jax.jit``'s trace cache: the first request
at every new batch shape pays a compile, which is fine for a Spark partition
sweep but is a multi-second latency cliff for an online endpoint. The engine
removes the cliff by **pre-compiling** the apply function for a ladder of
padded batch-size buckets (1, 2, 4, ... max_batch) at construction time via
``jit(...).lower(...).compile()`` — steady-state serving then never traces or
compiles again, whatever mix of request sizes arrives. Requests pad up to the
nearest bucket (bounded waste: < 2x rows) and trim on return; padded rows are
zeros, and row-independent graph evaluation means they can't perturb real
rows' outputs.

Sharding: with a multi-device ``dp`` mesh, buckets that divide over the axis
shard their batch (params replicated, exactly like the batch-transform path);
smaller buckets compile replicated rather than failing divisibility.

Quantized serving reuses :mod:`sparkflow_tpu.utils.quant`: the engine
quantizes the full-precision tree once at load and compiles the int8 apply —
``weight_only`` and ``dynamic`` both serve through the same bucket ladder.
"""

from __future__ import annotations

import hashlib
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis.runtime_guards import RecompileGuard
from ..core import _sharded_trace_guard
from ..obs.spans import span as obs_span
from ..sharding import as_sharding_config, per_device_bytes
from ..resilience import faults
from ..utils import metrics as metrics_mod
from ..utils.tracing import annotate


def _bucket_ladder(max_batch: int) -> List[int]:
    """1, 2, 4, ... up to max_batch (max_batch itself always included, so a
    non-power-of-two cap still has a full-size bucket)."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    buckets, b = [], 1
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch)
    return buckets


class InferenceEngine:
    """Low-latency predictions from a trained model, no steady-state compiles.

    Parameters
    ----------
    graph : str | model
        Model spec JSON (nn DSL / registry spec / TF1 metagraph — anything
        :func:`sparkflow_tpu.models.model_from_json` loads) or an already
        constructed model object.
    weights : list of arrays | str | params pytree | None
        Flat weight list, the estimator's weights Param (inline JSON or
        ``npz:<path>``), or an already-structured params pytree.
    input_name : str | sequence of str
        Input tensor name(s) (``'x:0'`` style); a sequence means requests
        carry a tuple of arrays (multi-input models).
    output_name : str
        Output tensor to serve.
    max_batch : int
        Top of the bucket ladder; larger requests run in max_batch chunks.
    mesh : jax.sharding.Mesh | None
        Serving mesh. With only a data axis, batches shard over it and
        params replicate. With a ``sharding`` config naming ``tp_axis`` /
        ``ep_axis`` present on the mesh, params shard per the model's
        megatron rules instead (attention/MLP on heads/hidden over tp,
        expert banks over ep) and GSPMD partitions each bucket's forward —
        tensor-parallel predict from the same config the trainer used.
    sharding : ShardingConfig | dict | None
        Declarative placement (``sparkflow_tpu.sharding.ShardingConfig``);
        serving consumes its ``data_axis``/``dcn_axis`` for batch rows and
        ``tp_axis``/``ep_axis`` for model-parallel params — the same config
        a Trainer fit used works here unchanged (zero stages only affect
        training). ``quantize`` does not compose with tp/ep.
    quantize : None | 'weight_only' | 'dynamic'
        int8 serving via ``utils.quant``. ``quant_min_size`` forwards to
        :func:`~sparkflow_tpu.utils.quant.quantize_params` (kernels below it
        stay full precision).
    warmup : bool
        AOT-compile every bucket at construction (default). With
        ``warmup=False``, buckets compile on first use (each counted in
        ``stats()['fallback_compiles']``).
    executable_dir : str | None
        Zero-compile cold start: a :class:`~sparkflow_tpu.serving.
        coldstart.ExecutableStore` directory of ``jax.export``-serialized
        executables. Warmup deserializes the bucket ladder from here
        (sha256-verified) instead of compiling; anything missing or stale
        compiles as usual — hitting ``compile_cache_dir`` when set — and
        is saved back for the next boot.
    """

    def __init__(self, graph, weights=None, *,
                 input_name: Union[str, Sequence[str]] = "x:0",
                 output_name: str = "out:0",
                 dropout_name: Optional[str] = None,
                 dropout_value: float = 1.0,
                 max_batch: int = 64,
                 mesh=None,
                 sharding=None,
                 quantize: Optional[str] = None,
                 quant_min_size: int = 4096,
                 compute_dtype=None,
                 warmup: bool = True,
                 compile_cache_dir: Optional[str] = None,
                 executable_dir: Optional[str] = None,
                 metrics: Optional[metrics_mod.Metrics] = None):
        if isinstance(graph, str):
            from ..models import model_from_json
            self.model = model_from_json(graph, compute_dtype)
        else:
            self.model = graph
        self.input_name = input_name
        self.output_name = output_name
        self.dropout_name = dropout_name
        self.dropout_value = dropout_value
        self.max_batch = int(max_batch)
        self.mesh = mesh
        self.sharding = as_sharding_config(sharding)
        if self.mesh is not None:
            # fail on a typo'd axis at construction, not first request; a
            # mesh without the data axis is fine (rows replicate)
            self.sharding.validate(self.mesh, require_data_axis=False)
            if (self.sharding.pp_axis is not None
                    and int(self.mesh.shape.get(self.sharding.pp_axis, 1))
                    > 1):
                raise ValueError(
                    "pp_axis is a decode-plane axis: the single-shot "
                    "predict engine has no token cadence to hide pipeline "
                    "bubbles behind. Serve depth-sharded models through "
                    "DecodeEngine (serving/decode.py), or drop pp_axis "
                    "from this engine's sharding config.")
        self.quantize = quantize
        self.metrics = metrics if metrics is not None else metrics_mod.Metrics()

        self._multi = isinstance(input_name, (list, tuple))
        names = list(input_name) if self._multi else [input_name]
        self._in_keys = [n.split(":")[0] for n in names]
        # validate names against the model's tensor table up front — a typo
        # must fail at engine construction, not on the first live request
        for n in names + [output_name]:
            self.model.graphdef.resolve(n)

        params = self._load_params(weights)
        # shape/dtype template of the ctor weights in STANDARD layout,
        # captured before quantize/shard: every hot swap validates against
        # it (shapes pinned unchanged so the AOT ladder is reused as-is)
        self._weights_template = jax.tree.map(
            lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype)
                       if hasattr(a, "dtype")
                       else jax.ShapeDtypeStruct(np.shape(a),
                                                 np.asarray(a).dtype)),
            params)
        # model-parallel predict: a config naming tp_axis/ep_axis present on
        # the mesh shards attention/MLP weights (megatron rules) and expert
        # banks instead of replicating — GSPMD partitions the matmuls and
        # inserts the all-reduces from the param shardings alone
        self._tp_specs = None
        self._quant_min_size = int(quant_min_size)
        mp = (self.mesh is not None
              and self.sharding.tp_size(self.mesh)
              * self.sharding.ep_size(self.mesh) > 1)
        if mp and quantize:
            raise ValueError("quantize does not compose with tensor/expert-"
                             "parallel serving (int8 packing breaks the "
                             "megatron layout); pick one")
        if quantize:
            from ..utils.quant import MODES
            if quantize not in MODES:
                raise ValueError(f"quantize must be one of {MODES} (or None), "
                                 f"got {quantize!r}")
            self.model.quant_mode = quantize
        if mp:
            if not hasattr(self.model, "param_pspecs"):
                raise TypeError("model-parallel serving needs the model to "
                                "publish param_pspecs() (megatron rules)")
            from ..parallel.tp import derive_param_pspecs, filter_pspec
            pspecs = derive_param_pspecs(self.model, self.mesh, self.sharding)
            self._tp_specs = jax.tree.map(
                lambda s: filter_pspec(s, self.mesh), pspecs,
                is_leaf=lambda x: isinstance(x, P))
        self._params = self._place_params(params)

        self._in_shapes, self._in_dtypes = self._input_layouts()
        self.buckets = _bucket_ladder(self.max_batch)
        self._compiled: Dict[int, Any] = {}
        self._compile_lock = threading.Lock()
        self._stats_lock = threading.Lock()  # request counters only
        # one expected trace per ladder bucket; anything beyond warns
        self.recompile_guard = RecompileGuard(name="serving.predict",
                                              warn_after=len(self.buckets))
        self.aot_compiles = 0
        self.fallback_compiles = 0
        self._requests = 0
        self._rows = 0
        self._serving_version = 0  # bumped by swap_params; 0 = ctor weights
        self._swaps = 0
        # persistent XLA compilation cache: with a directory set, warmup's
        # bucket compiles hit cached executables from earlier processes
        # instead of re-running XLA — the restart-latency knob. hits/misses
        # are estimated from cache-entry deltas around our own compiles.
        # The one helper decides the directory: JAX_COMPILATION_CACHE_DIR,
        # where set, wins over the argument.
        self.compile_cache_dir: Optional[str] = None
        self.compile_cache_hits = 0
        self.compile_cache_misses = 0
        if compile_cache_dir is not None:
            from ..utils.hw import enable_compilation_cache
            self.compile_cache_dir = enable_compilation_cache(
                compile_cache_dir)
        # zero-compile cold start: warmup loads jax.export-serialized
        # executables from here (sha256-manifested, ExecutableStore) before
        # falling back to compiling (which may hit the compile cache above),
        # and saves what it had to compile for the next boot
        self.exec_store = None
        self.serialized_loads = 0
        self.serialized_saves = 0
        self._exec_prefix = ""
        if executable_dir is not None:
            from .coldstart import ExecutableStore
            self.exec_store = ExecutableStore(executable_dir,
                                              metrics=self.metrics)
            # key signature over every shape-determining knob: a store
            # shared across differently-configured engines must never
            # deserialize a wrong-shaped program
            desc = repr((
                self._in_shapes, [str(d) for d in self._in_dtypes],
                self.quantize, self.output_name, self._in_keys,
                dict(self.mesh.shape) if self.mesh is not None else None,
                self.sharding.describe(),
                [(tuple(s.shape), str(s.dtype))
                 for s in jax.tree.leaves(self._weights_template)]))
            sig = hashlib.sha256(desc.encode()).hexdigest()[:12]
            self._exec_prefix = f"predict/{sig}"
        if warmup:
            self.warmup()

    # -- loading -------------------------------------------------------------

    @classmethod
    def from_checkpoint(cls, directory: str, graph, retry=None, **kwargs
                        ) -> "InferenceEngine":
        """Load from a :class:`~sparkflow_tpu.checkpoint.CheckpointManager`
        directory (``weights.npz`` export or an orbax training checkpoint,
        whose restore verifies manifest checksums and falls back past
        corrupt steps). ``retry`` (a
        :class:`~sparkflow_tpu.resilience.retry.RetryPolicy`) governs
        transient read errors — network filesystems at replica-start time
        are exactly the flaky window it exists for."""
        from ..checkpoint import CheckpointManager
        from ..models import model_from_json
        model = (model_from_json(graph, kwargs.get("compute_dtype"))
                 if isinstance(graph, str) else graph)
        weights = CheckpointManager.load_weights(directory, model,
                                                 retry=retry)
        return cls(model, weights, **kwargs)

    def _load_params(self, weights):
        from ..graphdef import list_to_params
        if weights is None:
            raise ValueError("weights are required (flat list, weights JSON, "
                             "'npz:<path>', or a params pytree)")
        if isinstance(weights, str):
            from ..ml_util import resolve_weights
            weights = resolve_weights(weights)
        if isinstance(weights, (list, tuple)):
            return list_to_params(self.model, list(weights))
        return weights  # already a params pytree

    def _place_params(self, params):
        """Quantize/shard/replicate one standard-layout tree into this
        engine's serving placement. The ctor and every hot swap run exactly
        this path, so a swapped tree lands bit-identical to a cold start."""
        if self.quantize:
            from ..utils.quant import quantize_params
            params = quantize_params(params, min_size=self._quant_min_size)
        if self._tp_specs is not None:
            from ..parallel.tp import shard_params
            params = shard_params(params, self.mesh, self._tp_specs)
        elif self.mesh is not None and self.mesh.size > 1:
            params = jax.device_put(params, NamedSharding(self.mesh, P()))
        return params

    def _snapshot_params(self):
        with self._stats_lock:
            return self._params

    def _input_layouts(self) -> Tuple[List[Tuple[int, ...]], List[Any]]:
        specs = self.model.input_specs()
        shapes, dtypes = [], []
        for key in self._in_keys:
            if key not in specs:
                raise KeyError(f"input {key!r} is not a model input; inputs: "
                               f"{sorted(specs)}")
            shape, dtype = specs[key]
            if any(d is None for d in shape[1:]):
                raise ValueError(
                    f"input {key!r} has non-static feature dims {shape}; the "
                    f"bucket ladder needs fully static row shapes")
            shapes.append(tuple(int(d) for d in shape[1:]))
            dtypes.append(np.dtype(dtype))
        return shapes, dtypes

    # -- compilation ---------------------------------------------------------

    def _apply_fn(self):
        model = self.model
        in_keys, multi = self._in_keys, self._multi
        drop_key = (self.dropout_name.split(":")[0]
                    if self.dropout_name else None)
        drop_val = self.dropout_value
        out_name = self.output_name

        def predict(params, x):
            import jax.numpy as jnp
            feeds = dict(zip(in_keys, tuple(x) if multi else (x,)))
            if drop_key is not None:
                feeds[drop_key] = jnp.asarray(drop_val, jnp.float32)
            return model.apply(params, feeds, [out_name],
                               train=False)[out_name]

        return predict

    def _x_struct(self, bucket: int):
        structs = tuple(
            jax.ShapeDtypeStruct((bucket,) + shape, dtype)
            for shape, dtype in zip(self._in_shapes, self._in_dtypes))
        return structs if self._multi else structs[0]

    def _compile_bucket(self, bucket: int):
        # guard-wrapped so every trace (one per bucket compile) is counted;
        # after warmup() marks steady state, any further trace is a
        # regression the ladder was supposed to prevent (GC-R401)
        predict = self.recompile_guard.wrap(self._apply_fn())
        params = self._snapshot_params()
        params_struct = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
            if not hasattr(a, "aval") else jax.ShapeDtypeStruct(a.shape, a.dtype),
            params)
        mesh = self.mesh
        if mesh is None or mesh.size <= 1:
            jitted = jax.jit(predict)
        else:
            predict = _sharded_trace_guard(predict, mesh)
            repl = NamedSharding(mesh, P())
            # params keep their megatron shardings under tp/ep, else replicate
            pshard = (jax.tree.map(lambda s: NamedSharding(mesh, s),
                                   self._tp_specs,
                                   is_leaf=lambda x: isinstance(x, P))
                      if self._tp_specs is not None else repl)
            # rows shard over the config's batch axes (data_axis + optional
            # dcn_axis) when the bucket divides their product, else replicate
            cfg = self.sharding
            dp = 1
            for a in cfg.batch_axes(mesh):
                dp *= mesh.shape[a]
            rows = (cfg.data_sharding(mesh)
                    if dp > 1 and bucket % dp == 0
                    else repl)
            data = (jax.tree.map(lambda _: rows, self._x_struct(bucket))
                    if self._multi else rows)
            jitted = jax.jit(predict, in_shardings=(pshard, data),
                             out_shardings=rows)
        if (mesh is not None and self.sharding.tp_size(mesh) > 1):
            # pallas flash attention has no GSPMD partitioning rule; tracing
            # under this context makes it nest its own shard_map over
            # batch x heads (falling back to the XLA blockwise path when the
            # dims don't divide the mesh axes)
            from ..ops.attention import sharded_attention
            with sharded_attention(mesh, batch_axis=self.sharding.data_axis,
                                   head_axis=self.sharding.tp_axis):
                return jitted.lower(params_struct,
                                    self._x_struct(bucket)).compile()
        return jitted.lower(params_struct, self._x_struct(bucket)).compile()

    def _cache_entries(self) -> int:
        if self.compile_cache_dir is None:
            return 0
        try:
            return len([f for f in os.listdir(self.compile_cache_dir)
                        if not f.startswith(".")])
        except OSError:
            return 0

    def warmup(self) -> None:
        """AOT-compile every bucket. Idempotent; after it returns,
        ``predict`` never compiles for any request size."""
        pending = []
        with self._compile_lock:
            before = self._cache_entries()
            compiled_now = 0
            for b in self.buckets:
                if b not in self._compiled:
                    # tier 1: deserialize a stored executable (no trace,
                    # no XLA); tiers 2/3: compile (hitting the persistent
                    # compile cache when configured), then store for the
                    # next boot
                    if self.exec_store is not None:
                        exe = self.exec_store.load(
                            f"{self._exec_prefix}/b{b}")
                        if exe is not None:
                            self._compiled[b] = exe
                            self.serialized_loads += 1
                            continue
                    with annotate(f"serving/aot_compile_b{b}"):
                        self._compiled[b] = self._compile_bucket(b)
                    self.aot_compiles += 1
                    compiled_now += 1
                    if self.exec_store is not None:
                        pending.append((f"{self._exec_prefix}/b{b}",
                                        self._compiled[b]))
            if self.compile_cache_dir is not None and compiled_now:
                # every compile either wrote a fresh cache entry (miss) or
                # loaded an existing one (hit); the dir delta splits them
                added = max(0, self._cache_entries() - before)
                misses = min(added, compiled_now)
                self.compile_cache_misses += misses
                self.compile_cache_hits += compiled_now - misses
            self.recompile_guard.mark_steady()
        # save-back AFTER the lock: ExecutableStore.save waits on the
        # cross-process manifest lock, and that wait must not stall
        # threads contending the compile lock (GC-L305)
        saved = sum(1 for key, exe in pending
                    if self.exec_store.save(key, exe))
        if saved:
            with self._compile_lock:
                self.serialized_saves += saved

    def _executable(self, bucket: int):
        exe = self._compiled.get(bucket)
        if exe is None:
            # lazy path (warmup=False) or a foreign bucket — counted so tests
            # can assert the steady state compiles nothing
            with self._compile_lock:
                exe = self._compiled.get(bucket)
                if exe is None:
                    exe = self._compiled[bucket] = self._compile_bucket(bucket)
                    self.fallback_compiles += 1
        return exe

    # -- serving -------------------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_batch

    def predict(self, x) -> np.ndarray:
        """Predict for ``x``: one array ``[n, ...]`` (or a tuple for
        multi-input models), any ``n >= 1``. Pads to the nearest bucket;
        requests beyond ``max_batch`` run in max_batch chunks."""
        faults.fire("engine.predict")  # chaos hook; no-op unless armed
        xs = tuple(np.asarray(a) for a in x) if self._multi \
            else (np.asarray(x),)
        if xs[0].ndim == len(self._in_shapes[0]):  # single unbatched row
            xs = tuple(a[None] for a in xs)
        for a, shape, key in zip(xs, self._in_shapes, self._in_keys):
            if tuple(a.shape[1:]) != shape:
                raise ValueError(
                    f"input {key!r}: rows have shape {tuple(a.shape[1:])}, "
                    f"model expects {shape}")
        n = xs[0].shape[0]
        if any(a.shape[0] != n for a in xs):
            raise ValueError("multi-input arrays must share the batch dim")
        # one params snapshot per request: a concurrent hot swap never gives
        # a chunked request mixed versions — every chunk runs the same tree
        params = self._snapshot_params()
        if n == 0:
            probe = self._run(tuple(a[:0] for a in xs), 0, params,
                              probe_rows=1)
            return probe[:0]
        with self._stats_lock:
            self._requests += 1
            self._rows += n
        if n > self.max_batch:
            outs = [self._run(tuple(a[i:i + self.max_batch] for a in xs),
                              min(self.max_batch, n - i), params)
                    for i in range(0, n, self.max_batch)]
            return np.concatenate(outs, axis=0)
        return self._run(xs, n, params)

    def _run(self, xs, n: int, params, probe_rows: int = 0) -> np.ndarray:
        have = max(n, probe_rows)
        bucket = self._bucket_for(have)
        if have < bucket:
            xs = tuple(np.concatenate(
                [a, np.zeros((bucket - a.shape[0],) + a.shape[1:], a.dtype)])
                for a in xs)
        elif probe_rows and xs[0].shape[0] == 0:
            xs = tuple(np.zeros((bucket,) + a.shape[1:], a.dtype) for a in xs)
        exe = self._executable(bucket)
        self.metrics.observe("serving/engine_batch_rows", n)
        self.metrics.observe("serving/padding_waste",
                             (bucket - n) / bucket if bucket else 0.0)
        # span + annotate: the host span routes to whatever tracer is
        # active on this thread (the batcher worker's, usually), and the
        # same named range still shows in JAX profiler captures
        with obs_span("serving/engine_apply", args={"bucket": bucket},
                      jax_annotation=True):
            out = exe(params, xs if self._multi else xs[0])
        return np.asarray(out)[:n]

    # -- live weight hot-swap ------------------------------------------------

    def weights_template(self):
        """Shape/dtype template (``ShapeDtypeStruct`` tree, standard layout)
        of the ctor weights — what a published tree must match leaf-for-leaf
        for :meth:`swap_params` to accept it."""
        return self._weights_template

    def swap_params(self, weights, *, version: Optional[int] = None) -> bool:
        """Hot-swap the serving weights without a restart. ``weights`` is
        anything the ctor accepts, in the model's STANDARD layout, with every
        leaf's shape/dtype identical to the ctor tree (enforced — the AOT
        bucket executables are reused as-is, so the swap causes zero
        retraces). Double-buffered: the new tree is quantized/sharded/placed
        on device while the old one keeps serving, then swapped in a single
        reference assignment; in-flight predicts hold their snapshot, so no
        request ever observes mixed versions. Returns True (swaps apply
        immediately on this engine)."""
        faults.fire("engine.swap")  # chaos hook; no-op unless armed
        params = self._load_params(weights)
        flat, treedef = jax.tree.flatten(params)
        want, want_def = jax.tree.flatten(self._weights_template)
        if treedef != want_def:
            raise ValueError("swapped weights have a different tree "
                             "structure than the ctor weights")
        for i, (got, w) in enumerate(zip(flat, want)):
            gshape = tuple(np.shape(got))
            gdtype = (np.dtype(got.dtype) if hasattr(got, "dtype")
                      else np.asarray(got).dtype)
            if gshape != tuple(w.shape) or gdtype != np.dtype(w.dtype):
                raise ValueError(
                    f"swapped weights leaf {i} is {gshape}/{gdtype}, "
                    f"expected {tuple(w.shape)}/{np.dtype(w.dtype)}: hot "
                    f"swap requires unchanged shapes")
        placed = self._place_params(params)  # old tree still serving
        with self._stats_lock:
            self._params = placed  # the swap: one reference assignment
            v = (int(version) if version is not None
                 else self._serving_version + 1)
            self._serving_version = v
            self._swaps += 1
        self.metrics.gauge("serving/version", float(v))
        return True

    def serving_version(self) -> int:
        """Version of the weights currently serving (0 = ctor weights)."""
        with self._stats_lock:
            return self._serving_version

    def maybe_swap(self) -> bool:
        """Swaps apply immediately on this engine; nothing is deferred."""
        return True

    # -- introspection -------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._stats_lock:
            requests, rows = self._requests, self._rows
            serving_version, swaps = self._serving_version, self._swaps
            params = self._params
        return {"buckets": list(self.buckets),
                "serving_version": serving_version,
                "swaps": swaps,
                "sharding": self.sharding.describe(),
                "aot_compiles": self.aot_compiles,
                "fallback_compiles": self.fallback_compiles,
                "traces": self.recompile_guard.traces,
                "steady_traces": self.recompile_guard.steady_traces,
                "requests": requests,
                "rows": rows,
                "compile_cache": (
                    None if self.compile_cache_dir is None else
                    {"dir": self.compile_cache_dir,
                     "hits": self.compile_cache_hits,
                     "misses": self.compile_cache_misses}),
                "cold_start": (
                    None if self.exec_store is None else
                    {"dir": self.exec_store.directory,
                     "serialized_loads": self.serialized_loads,
                     "serialized_saves": self.serialized_saves}),
                "quantize": self.quantize,
                "mesh": (dict(self.mesh.shape) if self.mesh is not None
                         else None),
                "tp": (self.sharding.tp_size(self.mesh)
                       if self.mesh is not None else 1),
                "ep": (self.sharding.ep_size(self.mesh)
                       if self.mesh is not None else 1),
                "param_bytes_per_device": sum(
                    per_device_bytes(leaf)
                    for leaf in jax.tree.leaves(params))}
