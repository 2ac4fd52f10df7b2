"""Autoregressive decode engine: AOT prefill ladder + fixed-shape paged decode.

The predict engine (:mod:`~sparkflow_tpu.serving.engine`) is single-shot:
one forward pass per request. LLM generation is a loop — one prefill over the
prompt, then one model step per generated token — and the loop is where both
recompiles and batching granularity can ruin throughput. This engine removes
both hazards the same way the predict engine removed its latency cliff:

- **Prefill** reuses the bucket-ladder idea: prompts pad to the nearest
  page-aligned bucket and run through an AOT-compiled
  (``jit(...).lower().compile()``) forward that captures every block's K/V
  (:meth:`~sparkflow_tpu.models.transformer.TransformerLM.prefill`) and
  commits it straight into the paged pool **inside the same executable** —
  the cache never round-trips through the host.
- **Decode** is ONE fixed-shape executable over the whole slot batch
  (``num_slots`` lanes), whatever subset of slots is live: token ids,
  positions, page tables and sampling knobs are dense ``[num_slots]``
  operands, inactive lanes compute garbage into the scratch page and are
  ignored by the host. Steady-state decode therefore never retraces —
  pinned by a :class:`~sparkflow_tpu.analysis.runtime_guards.RecompileGuard`
  exactly like the predict ladder.

Attention inside the decode step is the pallas
:func:`~sparkflow_tpu.ops.paged_attention` kernel over the page-table-
indirected K/V pool managed by :class:`~sparkflow_tpu.serving.kvcache.PagedKVCache`
(hooked in through ``TransformerLM.decode_step``'s ``attend`` callback, so
the model defines the architecture once and the engine only swaps the cache
layout).

Sampling is on-device, per slot, under an explicit PRNG key chain
(``[num_slots, 2]`` uint32 state, split once per sampling event): greedy when
``temperature == 0``, temperature + optional top-k otherwise (``top_k`` is
per-slot dynamic up to the static ``max_top_k`` compiled into the step).

Two prefill-cost optimizations ride on the paged indirection:

- **Shared-prefix caching** (``prefix_cache=True``): :meth:`prefill` hands the
  actual prompt tokens to the pool, which maps any indexed page-aligned
  prefix straight into the slot's table
  (:meth:`~sparkflow_tpu.serving.kvcache.PagedKVCache.alloc`). Only the
  un-shared suffix is forwarded, through a fixed-shape AOT **suffix
  executable** (``TransformerLM.prefill_suffix`` + a pool-writing attend);
  pages publish to the index only after their K/V is committed on device
  (``commit_prefix``). Greedy output is invariant to sharing — shared pages
  hold exactly the K/V the ladder would have recomputed.
- **Chunked prefill** (``prefill_chunk=N``): a prompt suffix longer than N
  no longer runs as one blocking ladder call. The slot is admitted
  immediately and its suffix advances one N-token chunk per :meth:`step`,
  **fused with the decode step in one device call** (one more AOT shape, not
  a ladder) — in-flight slots keep their token cadence while the long prompt
  streams in. Until its last chunk commits, the slot is masked out of the
  decode lanes (table row/position/token -> scratch page 0) so the
  fixed-shape step cannot touch half-committed pages; its first token is
  sampled at the final chunk and surfaces through :meth:`step`'s result.

**Speculative decoding** (``spec_k=N``) turns the one-token step into a
multi-token one: a cheap draft proposes ``k`` tokens per slot (either
*self-speculation* — the first ``draft_layers`` blocks of the same model
running over the same paged pool, whose layer-i K/V is identical to the
target's — or a separately supplied small ``draft_model`` with its own dense
cache), then ONE fixed-shape verify call
(:meth:`~sparkflow_tpu.models.transformer.TransformerLM.decode_verify` over
:func:`~sparkflow_tpu.ops.paged_attention_verify`) scores all ``k + 1``
positions for every live slot. The longest draft prefix matching the
target's greedy argmax commits — plus the target's own "bonus" token at the
first mismatch — and the rejected suffix rolls back through
:meth:`PagedKVCache.truncate`, which reuses the refcount/free/COW machinery
(a rollback that reaches into a shared page un-aliases it, never writes it).
Greedy output is token-identical to non-speculative decode by construction;
temperature slots simply run with a zero-width window (their bonus token is
sampled from the verify logits with the same per-slot key cadence as the
plain step). Draft + verify + rollback-copy are a bounded set of extra AOT
shapes, so the zero-steady-state-retrace invariant holds unchanged.

**Pipeline-parallel decode** (``sharding`` naming a ``pp_axis``) splits the
transformer's depth into ``pp`` stages: each stage holds only its own
blocks' weights and its own LAYERS-slice of the paged pool, activations hop
stage-to-stage on a ``ppermute`` ring inside the same shard_map that
carries tp, and every staged program keeps the no-cond discipline (all
stages compute every pass; inactive stages select their output away and
write K/V to scratch) so no collective ever sits under data-dependent
control flow. The naive staged step idles ``pp - 1`` stages per token, so
**micro-token wave scheduling** (``pp_wave=True``) partitions the live
slots into ``pp`` waves that occupy the pipeline simultaneously: one tick
per :meth:`step`, stage ``s`` decoding wave ``(t - s) mod pp``, one
fixed-shape AOT tick executable, zero steady-state retraces.

The engine is mechanism only — slot admission at token boundaries, queueing,
futures and drain semantics live in
:class:`~sparkflow_tpu.serving.batcher.ContinuousBatcher`.
"""

from __future__ import annotations

import hashlib
import math
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis.runtime_guards import RecompileGuard
from ..obs.spans import span as obs_span
from ..resilience import faults
from ..ops import paged_attention, paged_attention_verify
from ..ops.attention import record_attention_paths
from ..utils import metrics as metrics_mod
from ..utils import quant
from ..utils.tracing import annotate
from ..sharding import per_device_bytes
from .kvcache import OutOfPages, PagedKVCache

__all__ = ["DecodeEngine"]


def _prefill_ladder(page_size: int, max_prompt: int) -> List[int]:
    """Page-aligned bucket ladder: page, 2*page, 4*page, ... capped at
    ``max_prompt`` (itself included, already page-aligned)."""
    buckets, b = [], page_size
    while b < max_prompt:
        buckets.append(b)
        b *= 2
    buckets.append(max_prompt)
    return buckets


class DecodeEngine:
    """Continuous-decode mechanism over a paged KV cache.

    Parameters
    ----------
    model : TransformerLM | str
        A causal LM exposing ``prefill`` / ``decode_step`` (or a registry
        spec JSON that loads to one).
    params : pytree | list
        Trained parameters (flat weight list accepted, as in
        :class:`~sparkflow_tpu.serving.engine.InferenceEngine`).
    num_slots : int
        Decode lanes — the fixed batch dimension of the decode step.
    page_size : int
        KV-cache page size in tokens.
    num_pages : int | None
        Pool size including the scratch page. Default fully provisions
        every slot's worst case (``num_slots * max_pages_per_slot + 1``);
        undersize it to exercise admission backpressure.
    max_seq_len : int | None
        Per-sequence cap (prompt + generated), default the largest
        page-aligned length ``<= model.max_len``.
    max_top_k : int
        Static top-k ceiling compiled into the sampler; per-request
        ``top_k`` values clamp to it.
    prefill_chunk : int | None
        Enable chunked prefill: prompt suffixes longer than this advance one
        chunk per :meth:`step`, fused with the decode step in one device
        call. None (default) keeps the blocking ladder/suffix prefill.
    prefix_cache : bool
        Enable shared-prefix KV caching (on by default): prompts share
        page-aligned prefix K/V through the pool's refcounted prefix index
        and only prefill their un-shared suffix.
    spec_k : int
        Speculative window: draft up to ``spec_k`` tokens per slot per step
        and verify them (plus a bonus token) in one target call. 0 (default)
        disables speculation — :meth:`step` still returns token *lists*, of
        length 1.
    draft_layers : int | None
        Self-speculation depth: the draft is the target's first
        ``draft_layers`` blocks over the same paged pool. Default (with
        ``spec_k > 0`` and no ``draft_model``) is ``num_layers // 2``.
    draft_model, draft_params
        A separately trained small causal LM (same vocab) used as the draft
        instead of self-speculation; it keeps its own dense KV cache and
        prefills at admission through its own AOT ladder.
    mesh : jax.sharding.Mesh | None
        Serving mesh for model-parallel decode. With a ``sharding`` config
        naming ``tp_axis`` / ``ep_axis`` / ``pp_axis`` present on this
        mesh, every decode-plane executable becomes a shard_map over those
        axes: attention/MLP weights and the KV pool's heads axis shard over
        tp (each shard runs the unmodified pallas kernels on its own head
        slice, one psum after the O-projection / MLP rejoins activations),
        expert banks shard over ep, and transformer DEPTH shards over pp —
        blocks split into ``pp`` stages (the ``parallel/pp.py`` layout),
        the pool's layers axis shards with them, and activations hand
        stage-to-stage on a ``ppermute`` ring inside the same shard_map
        (``pp x tp`` composes as a 2D mesh; pp + ep is refused). Greedy
        output is token-identical to the unsharded engine; an external
        ``draft_model`` stays replicated off the mesh.
    sharding : ShardingConfig | dict | str | None
        Declarative axis naming (see :mod:`sparkflow_tpu.sharding`). Only
        ``tp_axis`` / ``ep_axis`` / ``pp_axis`` are consulted here; axes
        absent from the mesh (or of size 1) deactivate, so one config
        serves both sharded and single-device deployments.
    pp_wave : bool
        Micro-token wave scheduling (on by default, effective only with an
        active ``pp_axis`` and ``spec_k == 0``): live slots partition into
        ``pp`` waves that occupy the pipeline simultaneously — each
        :meth:`step` is one tick in which stage ``s`` decodes wave
        ``(t - s) mod pp``, so every stage stays busy and the pipeline
        bubble survives only at drain/refill edges. ``False`` keeps the
        single-wave staged step (all slots traverse all stages per call —
        same tokens, ``(pp-1)/pp`` of the mesh idle at any instant).
    kv_quant : str | None
        Pool element layout: ``None``/``"bf16"`` keeps the compute-dtype
        pool; ``"int8"`` / ``"fp8"`` store quantized rows plus a
        per-page-per-head f32 scale tensor kept alongside the page tables
        — roughly 2x (int8 vs bf16) the concurrent sessions per device.
        Every attend gathers quantized pages and dequantizes INSIDE the
        kernel accumulations (:func:`~sparkflow_tpu.ops.paged_attention`
        with ``k_scales``/``v_scales``); writes quantize at append time
        with a running per-page absmax. Composes with tp (scales shard on
        heads), pp (scales shard on layers), speculation (rollback
        ``truncate`` returns quantized pages to the reservation unchanged)
        and prefix/COW sharing (aliased table entries gather the same
        quantized rows) — same AOT shape count, zero steady-state
        retraces.
    """

    def __init__(self, model, params, *, num_slots: int = 8,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 max_seq_len: Optional[int] = None, max_top_k: int = 64,
                 seed: int = 0, warmup: bool = True,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = True,
                 spec_k: int = 0, draft_layers: Optional[int] = None,
                 draft_model=None, draft_params=None,
                 mesh=None, sharding=None, pp_wave: bool = True,
                 kv_quant: Optional[str] = None,
                 executable_dir: Optional[str] = None,
                 metrics: Optional[metrics_mod.Metrics] = None):
        if isinstance(model, str):
            from ..models import model_from_json
            model = model_from_json(model)
        if getattr(model, "decode_unsupported", None):
            raise TypeError(f"DecodeEngine cannot serve this model: "
                            f"{model.decode_unsupported}")
        for need in ("prefill", "decode_step"):
            if not hasattr(model, need):
                raise TypeError(f"model has no {need}(); DecodeEngine needs "
                                f"a causal LM (transformer_lm)")
        self.model = model
        # model-parallel serving: a ShardingConfig naming tp_axis/ep_axis on
        # a mesh turns every decode-plane executable into a shard_map over
        # those axes — attention/MLP weights and the KV pool's heads axis
        # shard over tp, expert banks over ep, activations stay replicated.
        # tp * ep == 1 keeps the exact single-device program (no wrapper).
        self.mesh = mesh
        self.sharding = None
        self._tp_axis: Optional[str] = None
        self._ep_axis: Optional[str] = None
        self._pp_axis: Optional[str] = None
        self._tp = 1
        self._ep = 1
        self._pp = 1
        if sharding is not None:
            from ..sharding import as_sharding_config
            self.sharding = as_sharding_config(sharding)
            if mesh is None and self.sharding.model_parallel():
                raise ValueError("sharding names tp_axis/ep_axis/pp_axis but "
                                 "no mesh was given; pass mesh= to "
                                 "DecodeEngine")
        if self.mesh is not None and self.sharding is not None:
            self.sharding.validate(self.mesh, require_data_axis=False)
            tp_ax, ep_ax = self.sharding.tp_axis, self.sharding.ep_axis
            pp_ax = self.sharding.pp_axis
            if tp_ax and int(self.mesh.shape[tp_ax]) > 1:
                self._tp_axis, self._tp = tp_ax, int(self.mesh.shape[tp_ax])
            if ep_ax and int(self.mesh.shape[ep_ax]) > 1:
                self._ep_axis, self._ep = ep_ax, int(self.mesh.shape[ep_ax])
            if pp_ax and int(self.mesh.shape[pp_ax]) > 1:
                self._pp_axis, self._pp = pp_ax, int(self.mesh.shape[pp_ax])
        self._sharded = self._tp * self._ep * self._pp > 1
        if self._tp > 1 and int(model.num_heads) % self._tp:
            raise ValueError(f"num_heads={model.num_heads} is not divisible "
                             f"by tp={self._tp}")
        if self._ep > 1:
            n_exp = getattr(model, "num_experts", None)
            if not n_exp:
                raise ValueError("ep_axis is set but the model has no expert "
                                 "bank (num_experts); use a transformer_moe_lm")
            if int(n_exp) % self._ep:
                raise ValueError(f"num_experts={n_exp} is not divisible by "
                                 f"ep={self._ep}")
        if self._pp > 1:
            if self._ep > 1:
                raise ValueError(
                    "pp_axis does not compose with ep_axis: expert dispatch "
                    "reduces inside the block body, which the staged no-cond "
                    "schedule would re-run on every stage. Shard depth (pp) "
                    "x width (tp) instead.")
            if int(model.num_layers) % self._pp:
                raise ValueError(
                    f"num_layers={model.num_layers} is not divisible by "
                    f"pp={self._pp}: each pipeline stage must hold the same "
                    f"number of blocks")
            for need in ("decode_embed", "block_decode", "decode_head"):
                if not hasattr(model, need):
                    raise TypeError(
                        f"pipeline-parallel decode needs the model to expose "
                        f"stage-level pieces ({need}()); use a "
                        f"transformer_lm")
        if self._sharded and not hasattr(model, "param_pspecs"):
            raise TypeError("model-parallel decode needs the model to "
                            "publish param_pspecs() (megatron rules)")
        self.metrics = metrics if metrics is not None else metrics_mod.Metrics()
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        cap = (self.page_size
               * (int(model.max_len) // self.page_size))
        if cap < self.page_size:
            raise ValueError(
                f"model.max_len={model.max_len} is below one page "
                f"(page_size={page_size})")
        self.max_seq_len = int(max_seq_len) if max_seq_len else cap
        if self.max_seq_len > int(model.max_len):
            raise ValueError(f"max_seq_len={self.max_seq_len} exceeds the "
                             f"model's max_len={model.max_len}")
        self.max_pages_per_slot = math.ceil(self.max_seq_len / self.page_size)
        if num_pages is None:
            num_pages = self.num_slots * self.max_pages_per_slot + 1
        # quantized-pool layout: validated here (construction) so a
        # misconfigured replica fails fast, not at first decode
        self.kv_quant = ("bf16" if kv_quant in (None, "bf16")
                         else str(kv_quant))
        if self.kv_quant not in quant.KV_DTYPES:
            raise ValueError(f"kv_quant must be one of {quant.KV_DTYPES} or "
                             f"None, got {kv_quant!r}")
        if not quant.kv_quant_supported(self.kv_quant):
            raise ValueError(
                "kv_quant='fp8' needs jax.numpy.float8_e4m3fn, which this "
                "jax/ml_dtypes install does not expose; use 'int8'")
        self._quantized = self.kv_quant != "bf16"
        self._kv_quant_error = None  # warmup probe: max |logit delta| vs bf16
        # device bytes one page costs across K + V (+ scales) and all
        # layers: the fleet surface routes on BYTE headroom, not raw page
        # counts, so replicas with different pool layouts compare fairly
        _cdt = (model.compute_dtype if model.compute_dtype is not None
                else jnp.float32)
        _item = 1 if self._quantized else np.dtype(_cdt).itemsize
        self._kv_bytes_per_page = 2 * int(model.num_layers) * (
            self.page_size * int(model.num_heads) * int(model.head_dim)
            * _item + (int(model.num_heads) * 4 if self._quantized else 0))
        self.kv = PagedKVCache(num_pages, self.page_size, self.num_slots,
                               self.max_pages_per_slot, metrics=self.metrics,
                               kv_dtype=self.kv_quant,
                               kv_bytes_per_page=self._kv_bytes_per_page)
        self.max_top_k = max(1, min(int(max_top_k), int(model.vocab_size)))
        # prompts pad to page-aligned buckets; the ladder top also caps
        # admissible prompt length
        self.prefill_buckets = _prefill_ladder(
            self.page_size, self.page_size * (self.max_seq_len
                                              // self.page_size))
        self.max_prompt_len = self.prefill_buckets[-1]
        self.prefix_cache = bool(prefix_cache)
        self.prefill_chunk: Optional[int] = None
        if prefill_chunk:
            self.prefill_chunk = max(1, min(int(prefill_chunk),
                                            self.max_prompt_len))
        # static width of the suffix/fused executables: the chunk size when
        # chunking, else one page (prefix-hit suffixes are typically short)
        self._chunk_width = self.prefill_chunk or self.page_size

        # speculative decoding configuration
        self.spec_k = int(spec_k or 0)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.draft_layers: Optional[int] = None
        self._draft_model = None
        self._draft_params = None
        if self.spec_k:
            if draft_model is not None:
                if isinstance(draft_model, str):
                    from ..models import model_from_json
                    draft_model = model_from_json(draft_model)
                for need in ("prefill", "decode_step"):
                    if not hasattr(draft_model, need):
                        raise TypeError(f"draft_model has no {need}(); it "
                                        f"must be a causal LM")
                if int(draft_model.vocab_size) != int(model.vocab_size):
                    raise ValueError(
                        f"draft vocab_size={draft_model.vocab_size} != "
                        f"target vocab_size={model.vocab_size}")
                if draft_params is None:
                    raise ValueError("draft_model requires draft_params")
                if isinstance(draft_params, (list, tuple)):
                    from ..graphdef import list_to_params
                    draft_params = list_to_params(draft_model,
                                                  list(draft_params))
                self._draft_model = draft_model
                self._draft_params = draft_params
            else:
                L = (int(draft_layers) if draft_layers
                     else max(1, int(model.num_layers) // 2))
                if not 1 <= L <= int(model.num_layers):
                    raise ValueError(
                        f"draft_layers={L} outside [1, {model.num_layers}]")
                if self._pp > 1 and L % (int(model.num_layers) // self._pp):
                    raise ValueError(
                        f"draft_layers={L} must be a whole number of "
                        f"pipeline stages (stage depth = "
                        f"{int(model.num_layers) // self._pp}) so the "
                        f"self-speculation chain exits at a stage boundary")
                self.draft_layers = L
        elif draft_model is not None or draft_layers:
            raise ValueError("draft_model / draft_layers require spec_k >= 1")
        # micro-token wave scheduling: live slots partition into pp waves
        # that occupy the pipeline simultaneously (stage s decodes wave
        # (t - s) mod pp at tick t), amortizing the pipeline bubble away.
        # The speculative step already amortizes depth over its multi-token
        # chunk, so waves stand down when speculation is on.
        self._pp_wave = bool(pp_wave) and self._pp > 1 and not self.spec_k
        if self._pp_wave and self.num_slots % self._pp:
            raise ValueError(
                f"num_slots={num_slots} is not divisible by pp={self._pp}: "
                f"wave scheduling partitions the slot lanes into pp equal "
                f"waves (pass pp_wave=False for the single-wave schedule)")

        if isinstance(params, (list, tuple)):
            from ..graphdef import list_to_params
            params = list_to_params(model, list(params))
        # shape/dtype template of the ctor params in STANDARD layout
        # (pre-pack, pre-split): every hot swap validates against it, so the
        # compiled prefill/decode executables are reused with zero retraces
        self._weights_template = jax.tree.map(
            lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype)
                       if hasattr(a, "dtype")
                       else jax.ShapeDtypeStruct(np.shape(a),
                                                 np.asarray(a).dtype)),
            params)
        self._param_specs = None
        self._params = self._prepare_params(params)
        pool_dtype = (model.compute_dtype if model.compute_dtype is not None
                      else jnp.float32)
        # GLOBAL pool shape; under tp the heads axis shards across the mesh
        # ([layers, pages, page, heads/tp, d] per device) and under pp the
        # LAYERS axis shards ([layers/pp, ...] per stage — each stage
        # allocates and gathers only its own layers' pages), both of which
        # leave the pallas kernels' slot/page grids untouched — each shard
        # runs the unmodified kernel over its own layer/head slice. The
        # host-global page bookkeeping (refcounts, prefix trie, COW) is
        # layout-blind either way.
        pool_shape = (model.num_layers, num_pages, self.page_size,
                      model.num_heads, model.head_dim)
        rows_spec = (P(self._pp_axis, None, None, self._tp_axis, None)
                     if (self._tp_axis or self._pp_axis) else P())
        if self._quantized:
            # quantized pool: each pool becomes a (rows, scales) pytree —
            # int8/fp8 rows in the page layout plus [layers, pages, heads]
            # f32 scales. quant + tp shards the scales on HEADS with the
            # rows' heads axis; quant + pp shards them on LAYERS with the
            # stage split — the scale for a page-head always lives on the
            # shard that gathers those rows. Every AOT signature below is
            # positionally unchanged (the pool argument is just a pytree).
            store_dtype, _ = quant.kv_pool_dtype(self.kv_quant)
            scale_shape = (model.num_layers, num_pages, model.num_heads)
            scale_spec = (P(self._pp_axis, None, self._tp_axis)
                          if (self._tp_axis or self._pp_axis) else P())
            self._pool_spec = (rows_spec, scale_spec)

            def _mk_pool():
                rows = jnp.zeros(pool_shape, store_dtype)
                scales = jnp.zeros(scale_shape, jnp.float32)
                if self._sharded:
                    rows = jax.device_put(
                        rows, NamedSharding(self.mesh, rows_spec))
                    scales = jax.device_put(
                        scales, NamedSharding(self.mesh, scale_spec))
                return (rows, scales)

            self._k_pool = _mk_pool()
            self._v_pool = _mk_pool()
        else:
            self._pool_spec = rows_spec
            if self._sharded:
                ns = NamedSharding(self.mesh, self._pool_spec)
                self._k_pool = jax.device_put(
                    jnp.zeros(pool_shape, pool_dtype), ns)
                self._v_pool = jax.device_put(
                    jnp.zeros(pool_shape, pool_dtype), ns)
            else:
                self._k_pool = jnp.zeros(pool_shape, pool_dtype)
                self._v_pool = jnp.zeros(pool_shape, pool_dtype)
        if self._draft_model is not None:
            dm = self._draft_model
            # dense per-slot draft cache: positions can reach
            # max_seq_len - 1 + spec_k during a clamped-window chain, and
            # the final row is a write margin masked lanes are redirected
            # to (it is never attended — live queries stop one short of it)
            self._draft_cache_len = self.max_seq_len + self.spec_k + 1
            dshape = (dm.num_layers, self.num_slots, dm.num_heads,
                      self._draft_cache_len, dm.head_dim)
            ddt = (dm.compute_dtype if dm.compute_dtype is not None
                   else jnp.float32)
            self._draft_k = jnp.zeros(dshape, ddt)
            self._draft_v = jnp.zeros(dshape, ddt)
        # host-side key state: per-slot mutation is numpy indexing, and an
        # uncommitted host array places cleanly on whatever sharding each
        # executable expects (single-device and mesh executables coexist)
        self._keys = np.stack([np.asarray(jax.random.PRNGKey(seed + i))
                               for i in range(self.num_slots)])
        self._last_token = np.zeros(self.num_slots, np.int32)
        self._temp = np.zeros(self.num_slots, np.float32)
        self._topk = np.zeros(self.num_slots, np.int32)
        # slots mid-chunked-prefill are kv-active but not decode-ready: the
        # fixed-shape step masks them to scratch until their K/V is committed
        self._decode_ready = np.zeros(self.num_slots, bool)
        self._pending: List[Dict[str, Any]] = []  # chunked-prefill states
        # wave scheduling state: the stage-to-stage activation ring (a
        # [pp, W, 1, hidden] carry whose leading axis shards over pp_axis),
        # the tick counter, and which slots ride each in-flight wave
        self._x_carry = None
        self._tick = 0
        self._wave_inflight: Dict[int, List[int]] = {}
        if self._pp_wave:
            W = self.num_slots // self._pp
            xc = jnp.zeros((self._pp, W, 1, int(model.hidden)), pool_dtype)
            self._x_carry = jax.device_put(
                xc, NamedSharding(self.mesh, P(self._pp_axis)))
            self._wave_inflight = {w: [] for w in range(self._pp)}

        self._lock = threading.Lock()
        # expected traces: one per prefill bucket + decode + prefill sampler
        # + suffix prefill (+ the fused chunk/decode step when chunking);
        # speculation adds draft + verify + rollback page-copy, and an
        # external draft its own prefill ladder — all compiled in warmup
        spec_shapes = 0
        if self.spec_k:
            spec_shapes = 3 + (len(self.prefill_buckets)
                               if self._draft_model is not None else 0)
        self.recompile_guard = RecompileGuard(
            name="serving.decode",
            warn_after=len(self.prefill_buckets) + 3
            + (1 if self.prefill_chunk else 0)
            + (1 if self._pp_wave else 0) + spec_shapes)
        # zero-compile cold start: _aot_locked loads jax.export-serialized
        # executables from this store before compiling (sha256-manifested;
        # ExecutableStore) and saves what it compiled for the next boot.
        # The key embeds a signature over every shape-determining knob, so
        # a store shared across differently-configured engines never
        # deserializes a wrong-shaped program.
        self.exec_store = None
        self.serialized_loads = 0
        self.serialized_saves = 0
        # executables compiled under the engine lock, awaiting store
        # save-back — flushed after the lock is released (save() waits on
        # the cross-process manifest lock; that wait must not stall
        # threads contending the engine lock)
        self._pending_exec_saves = []
        self._exec_prefix = ""
        if executable_dir is not None:
            from .coldstart import ExecutableStore
            self.exec_store = ExecutableStore(executable_dir,
                                              metrics=self.metrics)
            desc = repr((
                self.num_slots, self.page_size, int(num_pages),
                self.max_pages_per_slot, self.max_seq_len, self.max_top_k,
                self._chunk_width, self.prefill_chunk, self.spec_k,
                self.draft_layers, self.kv_quant, self._pp_wave,
                self._tp, self._ep, self._pp,
                dict(self.mesh.shape) if self.mesh is not None else None,
                int(model.vocab_size),
                [(tuple(s.shape), str(s.dtype))
                 for s in jax.tree.leaves(self._weights_template)]))
            sig = hashlib.sha256(desc.encode()).hexdigest()[:12]
            self._exec_prefix = f"decode/{sig}"
        self._attention_paths: Dict[str, List[str]] = {}
        self._prefill_exes: Dict[int, Any] = {}
        self._decode_exe: Any = None
        self._sample_exe: Any = None
        self._suffix_exe: Any = None
        self._fused_exe: Any = None
        self._tick_exe: Any = None
        self._draft_exe: Any = None
        self._verify_exe: Any = None
        self._copy_exe: Any = None
        self._draft_prefill_exes: Dict[int, Any] = {}
        self.aot_compiles = 0
        self._steps = 0
        self._tokens_out = 0
        self._prefills = 0
        self._spec_steps = 0
        self._spec_slot_steps = 0   # per-slot participations in spec steps
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_draft_ms = 0.0
        self._spec_verify_ms = 0.0
        # hot-swap state (guarded by self._lock): a prepared-but-unapplied
        # (params, version) double buffer waiting for a drained token
        # boundary — no active slots, no chunked prefills in flight
        self._pending_swap: Optional[Tuple[Any, int]] = None
        self._serving_version = 0  # 0 = ctor weights
        self._swaps = 0
        if self._pp > 1:
            # the staged builders shadow the flat-stack methods on this
            # instance, so everything downstream — the _fused_fn
            # composition, warmup, prefill, step, the decode lint — picks
            # up the pipeline schedule without knowing it exists
            self._decode_fn = self._pp_decode_fn()
            self._prefill_fn = self._pp_prefill_fn
            self._suffix_fn = self._pp_suffix_fn
            self._self_draft_fn = self._pp_self_draft_fn
            self._verify_fn = self._pp_verify_fn
        if warmup:
            self.warmup()

    def _prepare_params(self, params):
        """Pack/split/shard one standard-layout tree into this engine's
        serving placement (tp column packing, pp stage split, GSPMD
        shardings). The ctor and every hot swap run exactly this path, so a
        swapped tree lands bit-identical to a cold start. Must be called
        OUTSIDE ``self._lock`` — device placement is the slow half of a swap
        and decode keeps serving the old tree meanwhile."""
        model = self.model
        if not self._sharded:
            return params
        from ..parallel.tp import (derive_param_pspecs, filter_pspec,
                                   shard_params, tp_pack_params)
        if self._tp > 1:
            # shard_map hands each rank a contiguous column block: permute
            # qkv columns to (tp, 3, H/tp, d) order and pre-divide the
            # row-parallel biases so the decode psums are exact
            params = tp_pack_params(model, params, self._tp)
        pspecs = derive_param_pspecs(model, self.mesh, self.sharding)
        if pspecs is None:
            # pp-only mesh: no tp/ep axis shards weight columns, every
            # leaf starts replicated (the stage split below re-lays the
            # block leaves out over pp_axis)
            pspecs = jax.tree.map(lambda s: P(), model.param_pspecs(),
                                  is_leaf=lambda x: isinstance(x, P))
        specs = jax.tree.map(
            lambda s: filter_pspec(s, self.mesh), pspecs,
            is_leaf=lambda x: isinstance(x, P))
        if self._pp > 1:
            # depth split (parallel/pp.py layout): per-block leaves
            # stack to [pp, layers/pp, ...] with the leading stage axis
            # sharded over pp_axis — each stage holds only its own
            # blocks' weights at rest. embed/final_ln replicate: every
            # stage runs entry/exit unconditionally in the no-cond
            # staged schedule, and the block leaves keep any megatron
            # tp columns behind the stage axes (2D pp x tp).
            from ..parallel.pp import (split_stage_params,
                                       split_stage_pspecs)
            params = split_stage_params(model, params, self._pp)
            specs = split_stage_pspecs(
                self._pp_axis, specs["block_0"],
                {k: v for k, v in specs.items()
                 if not k.startswith("block_")})
        self._param_specs = specs
        return shard_params(params, self.mesh, specs)

    # -- jitted functions ----------------------------------------------------

    def _sample_tokens(self, logits, keys, temp, topk):
        """Shared sampler: greedy lane when ``temp == 0``, temperature +
        per-slot top-k (clamped to the static ``max_top_k``) otherwise.
        Returns ``(tokens [B] int32, advanced keys [B, 2])``."""
        split = jax.vmap(jax.random.split)(keys)           # [B, 2, 2]
        sub, nxt = split[:, 0], split[:, 1]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        vals = jax.lax.top_k(logits, self.max_top_k)[0]    # [B, K] desc
        kidx = jnp.clip(topk - 1, 0, self.max_top_k - 1)
        thr = jnp.take_along_axis(vals, kidx[:, None], axis=1)
        masked = jnp.where(logits < thr, -1e30, logits)
        lg = jnp.where((topk > 0)[:, None], masked, logits)
        safe_t = jnp.where(temp > 0, temp, 1.0)[:, None]
        sampled = jax.vmap(jax.random.categorical)(sub, lg / safe_t)
        tok = jnp.where(temp > 0, sampled.astype(jnp.int32), greedy)
        return tok, nxt

    # -- pool-layout helpers -------------------------------------------------
    #
    # With kv_quant on, each pool is a (rows int8/fp8, scales f32) pytree;
    # these keep the attend closures layout-agnostic. The branch is on a
    # python bool fixed at construction, so each engine traces exactly one
    # layout — no data-dependent control flow enters the jaxprs.

    def _kv_rows(self, pool, layer, pids, offs, rows):
        """Scatter token rows at ``(layer, pids, offs)``; any batch shape.
        Quantized pools maintain the running per-page-per-head scale."""
        if self._quantized:
            return quant.paged_quant_append(pool[0], pool[1], layer,
                                            pids, offs, rows)
        return pool.at[layer, pids, offs].set(rows.astype(pool.dtype))

    def _kv_pages(self, pool, layer, page_ids, pages):
        """Commit whole pages at ``(layer, page_ids)`` (ladder prefill)."""
        if self._quantized:
            return quant.paged_quant_write_pages(pool[0], pool[1], layer,
                                                 page_ids, pages)
        return pool.at[layer, page_ids].set(pages.astype(pool.dtype))

    def _kv_heads(self, pool):
        """``(local heads, head_dim)`` of a pool regardless of layout."""
        a = pool[0] if self._quantized else pool
        return a.shape[-2], a.shape[-1]

    def _kv_gather(self, pool, layer, page_ids):
        """Gather pages to f32 rows ``[..., page, heads, d]``, dequantizing
        the gathered rows only (never the whole pool — GC-J108)."""
        if self._quantized:
            return quant.paged_quant_gather(pool[0], pool[1], layer,
                                            page_ids)
        return pool[layer, page_ids].astype(jnp.float32)

    def _paged_att(self, q, kp, vp, layer, table, lengths):
        if self._quantized:
            return paged_attention(q, kp[0][layer], vp[0][layer], table,
                                   lengths, k_scales=kp[1][layer],
                                   v_scales=vp[1][layer])
        return paged_attention(q, kp[layer], vp[layer], table, lengths)

    def _paged_verify_att(self, q, kp, vp, layer, table, start):
        if self._quantized:
            return paged_attention_verify(q, kp[0][layer], vp[0][layer],
                                          table, start,
                                          k_scales=kp[1][layer],
                                          v_scales=vp[1][layer])
        return paged_attention_verify(q, kp[layer], vp[layer], table, start)

    def _decode_fn(self, params, k_pool, v_pool, token, pos, table, keys,
                   temp, topk):
        page = self.page_size
        bidx = jnp.arange(self.num_slots)

        def attend(layer, q, k_new, v_new, cache, p):
            kp, vp = cache
            page_ids = table[bidx, p // page]
            off = p % page
            kp = self._kv_rows(kp, layer, page_ids, off, k_new)
            vp = self._kv_rows(vp, layer, page_ids, off, v_new)
            out = self._paged_att(q, kp, vp, layer, table, p + 1)
            return out.astype(q.dtype), (kp, vp)

        logits, (k_pool, v_pool) = self.model.decode_step(
            params, (k_pool, v_pool), token, pos, attend=attend,
            tp_axis=self._tp_axis, ep_axis=self._ep_axis)
        tok, keys = self._sample_tokens(logits, keys, temp, topk)
        return tok, k_pool, v_pool, keys

    def _prefill_fn(self, bucket: int):
        model, page = self.model, self.page_size
        npages = bucket // page

        def prefill(params, k_pool, v_pool, ids, length, page_ids):
            # causal attention makes valid rows independent of the padded
            # tail, so no kv_mask is needed; the padded tail's K/V lands in
            # positions >= length, which decode attention masks by length
            logits, kvs = model.prefill(params, ids, lengths=length,
                                        tp_axis=self._tp_axis,
                                        ep_axis=self._ep_axis)
            for i, (k, v) in enumerate(kvs):
                # [1, heads, bucket, d] -> [npages, page, heads, d]; the
                # head count comes from the tensor (the shard's LOCAL heads
                # under tp — matching its heads-slice of the pool)
                kk = jnp.transpose(k[0], (1, 0, 2)).reshape(
                    npages, page, k.shape[1], k.shape[3])
                vv = jnp.transpose(v[0], (1, 0, 2)).reshape(
                    npages, page, v.shape[1], v.shape[3])
                k_pool = self._kv_pages(k_pool, i, page_ids, kk)
                v_pool = self._kv_pages(v_pool, i, page_ids, vv)
            return logits, k_pool, v_pool

        return prefill

    def _suffix_fn(self):
        """Fixed-shape suffix prefill: forward one ``_chunk_width``-token
        chunk of a prompt whose first ``start`` tokens' K/V is already
        committed in the slot's pages (shared prefix and/or earlier chunks),
        writing the chunk's K/V into the slot's pages and attending over the
        whole history through the page table. One batch row — chunks are
        per-slot events, the decode hot path stays the pallas kernel."""
        model, page, C = self.model, self.page_size, self._chunk_width
        maxp = self.max_pages_per_slot
        scale = 1.0 / math.sqrt(model.head_dim)
        j = jnp.arange(C, dtype=jnp.int32)
        tpos = jnp.arange(maxp * page, dtype=jnp.int32)

        def suffix_prefill(params, k_pool, v_pool, ids, start, valid, ctable):
            def attend(layer, q, k_new, v_new, cache, st):
                kp, vp = cache
                heads, hd = self._kv_heads(kp)                 # local under tp
                pos_abs = st[0] + j                            # [C] absolute
                pids = ctable[jnp.clip(pos_abs // page, 0, maxp - 1)]
                pids = jnp.where(j < valid[0], pids, 0)        # pad -> scratch
                off = pos_abs % page
                kc = jnp.transpose(k_new[0], (1, 0, 2))        # [C, heads, d]
                vc = jnp.transpose(v_new[0], (1, 0, 2))
                kp = self._kv_rows(kp, layer, pids, off, kc)
                vp = self._kv_rows(vp, layer, pids, off, vc)
                # gather the row's pages in logical order: element l of the
                # flattened gather sits at absolute position l
                hk = self._kv_gather(kp, layer, ctable).reshape(
                    maxp * page, heads, hd)
                hv = self._kv_gather(vp, layer, ctable).reshape(
                    maxp * page, heads, hd)
                s = jnp.einsum("hcd,lhd->hcl", q[0].astype(jnp.float32),
                               hk) * scale
                ok = tpos[None, :] <= pos_abs[:, None]         # causal [C, L]
                s = jnp.where(ok[None, :, :], s, -1e30)
                p = jax.nn.softmax(s, axis=-1)
                out = jnp.einsum("hcl,lhd->hcd", p, hv)
                return out[None].astype(q.dtype), (kp, vp)

            logits, (k_pool, v_pool) = model.prefill_suffix(
                params, ids, start, (k_pool, v_pool), attend, lengths=valid,
                tp_axis=self._tp_axis, ep_axis=self._ep_axis)
            return logits, k_pool, v_pool

        return suffix_prefill

    def _fused_fn(self):
        """Chunked prefill's device call: one suffix chunk + the regular
        fixed-shape decode step, fused so in-flight slots pay one dispatch —
        not a prefill stall — while a long prompt streams in."""
        body = self._suffix_fn()
        decode = self._decode_fn

        def fused(params, k_pool, v_pool, ids, start, valid, ctable,
                  token, pos, table, keys, temp, topk):
            logits, k_pool, v_pool = body(params, k_pool, v_pool, ids,
                                          start, valid, ctable)
            tok, k_pool, v_pool, keys = decode(params, k_pool, v_pool,
                                               token, pos, table, keys,
                                               temp, topk)
            return logits, tok, k_pool, v_pool, keys

        return fused

    def _self_draft_fn(self):
        """Self-speculation draft: an unrolled ``spec_k``-step greedy chain
        through the target's first ``draft_layers`` blocks, reading and
        writing the *same* paged pool the verify pass uses — valid because a
        truncated stack's layer-i K/V is identical to the full stack's, and
        safe because the verify pass overwrites every chunk position anyway.
        Writes past a slot's appended room are masked to the scratch page."""
        model, page, maxp = self.model, self.page_size, self.max_pages_per_slot
        K, Ld = self.spec_k, self.draft_layers
        bidx = jnp.arange(self.num_slots)

        def draft(params, k_pool, v_pool, token, pos, table, nappend):
            writable = pos + nappend        # first position with no room

            def attend(layer, q, k_new, v_new, cache, p):
                kp, vp = cache
                pids = table[bidx, jnp.clip(p // page, 0, maxp - 1)]
                pids = jnp.where(p < writable, pids, 0)
                off = p % page
                kp = self._kv_rows(kp, layer, pids, off, k_new)
                vp = self._kv_rows(vp, layer, pids, off, v_new)
                out = self._paged_att(q, kp, vp, layer, table, p + 1)
                return out.astype(q.dtype), (kp, vp)

            toks, tok = [], token
            for j in range(K):
                logits, (k_pool, v_pool) = model.decode_step(
                    params, (k_pool, v_pool), tok, pos + j, attend=attend,
                    num_layers=Ld, tp_axis=self._tp_axis,
                    ep_axis=self._ep_axis)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                toks.append(tok)
            return jnp.stack(toks, axis=1), k_pool, v_pool

        return draft

    def _ext_draft_fn(self):
        """External-draft chain: the small draft model's greedy ``spec_k``
        steps over its own dense per-slot cache. Rejected positions leave
        stale draft K/V behind, but the next chain starting at the commit
        point overwrites each position before anything attends to it; dead
        lanes write to the cache's margin row (never attended)."""
        dm, K = self._draft_model, self.spec_k
        CL = self._draft_cache_len
        bidx = jnp.arange(self.num_slots)
        scale = 1.0 / math.sqrt(dm.head_dim)
        lpos = jnp.arange(CL, dtype=jnp.int32)

        def draft(params, ck, cv, token, pos, live):
            def attend(layer, q, k_new, v_new, cache, p):
                ck, cv = cache
                p_eff = jnp.where(live, p, CL - 1)
                k = ck[layer].at[bidx, :, p_eff].set(k_new.astype(ck.dtype))
                v = cv[layer].at[bidx, :, p_eff].set(v_new.astype(cv.dtype))
                s = jnp.einsum("bhd,bhld->bhl", q.astype(jnp.float32),
                               k.astype(jnp.float32)) * scale
                ok = lpos[None, :] <= p[:, None]
                s = jnp.where(ok[:, None, :], s, -1e30)
                pr = jax.nn.softmax(s, axis=-1)
                out = jnp.einsum("bhl,bhld->bhd", pr, v.astype(jnp.float32))
                return (out.astype(q.dtype),
                        (ck.at[layer].set(k), cv.at[layer].set(v)))

            toks, tok = [], token
            for j in range(K):
                logits, (ck, cv) = dm.decode_step(
                    params, (ck, cv), tok, pos + j, attend=attend)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                toks.append(tok)
            return jnp.stack(toks, axis=1), ck, cv

        return draft

    def _ext_draft_prefill_fn(self, bucket: int):
        """Draft-cache prefill for one ladder bucket: forward the (padded)
        prompt through the draft model and write its K/V into ``slot``'s
        dense cache lane. Padding garbage past ``length`` is harmless — the
        first draft chain overwrites position ``length`` before attending."""
        dm = self._draft_model

        def dprefill(params, ck, cv, ids, length, slot):
            _logits, kvs = dm.prefill(params, ids, lengths=length)
            for i, (k, v) in enumerate(kvs):
                # k/v [1, heads, bucket, d] -> lane update at (i, slot, 0, 0)
                ck = jax.lax.dynamic_update_slice(
                    ck, k[None].astype(ck.dtype), (i, slot, 0, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cv, v[None].astype(cv.dtype), (i, slot, 0, 0, 0))
            return ck, cv

        return dprefill

    def _verify_fn(self):
        """One fixed-shape target call scoring all ``spec_k + 1`` chunk
        positions per slot: write the chunk's K/V into the slot's pages
        (lanes masked past ``nvalid`` -> scratch), attend per-query-causally
        over the whole table (:func:`paged_attention_verify`), and return
        the greedy argmax at every position plus a sampled token from
        position 0 (the temperature lanes' bonus — one sampler advance per
        verify keeps the per-token key cadence of the plain step)."""
        model, page, maxp = self.model, self.page_size, self.max_pages_per_slot
        S = self.spec_k + 1
        bidx = jnp.arange(self.num_slots)
        j = jnp.arange(S, dtype=jnp.int32)

        def verify(params, k_pool, v_pool, ids, start, nvalid, table, keys,
                   temp, topk):
            def attend(layer, q, k_new, v_new, cache, st):
                kp, vp = cache
                pos_abs = st[:, None] + j[None, :]             # [B, S]
                pids = table[bidx[:, None],
                             jnp.clip(pos_abs // page, 0, maxp - 1)]
                pids = jnp.where(j[None, :] < nvalid[:, None], pids, 0)
                off = pos_abs % page
                kc = jnp.transpose(k_new, (0, 2, 1, 3))    # [B, S, heads, d]
                vc = jnp.transpose(v_new, (0, 2, 1, 3))
                kp = self._kv_rows(kp, layer, pids, off, kc)
                vp = self._kv_rows(vp, layer, pids, off, vc)
                out = self._paged_verify_att(q, kp, vp, layer, table, st)
                return out.astype(q.dtype), (kp, vp)

            logits, (k_pool, v_pool) = model.decode_verify(
                params, ids, start, (k_pool, v_pool), attend,
                tp_axis=self._tp_axis, ep_axis=self._ep_axis)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, S]
            samp0, keys = self._sample_tokens(logits[:, 0], keys, temp, topk)
            return g, samp0, k_pool, v_pool, keys

        return verify

    def _copy_pages_fn(self, k_pool, v_pool, src, dst):
        """Rollback COW un-alias: clone pool page ``src`` into ``dst`` (all
        layers). Compiled once at warmup; reached only when a truncate
        crosses into a shared page, which in-engine rollback provably never
        does (the floor is past the shared prompt) — kept so even the
        pathological path cannot retrace steady state. Axis 1 is the pages
        axis of both the row tensors and the quantized scale planes, so one
        tree.map clones rows AND scales."""
        cp = lambda a: a.at[:, dst].set(a[:, src])
        return jax.tree.map(cp, k_pool), jax.tree.map(cp, v_pool)

    # -- pipeline-parallel staged builders -----------------------------------
    #
    # With pp_axis active these closures SHADOW the flat-stack builders
    # above (see __init__): same signatures, same AOT plumbing, but the body
    # is a staged schedule inside the shard_map. Design rules:
    #
    # - no-cond: every stage executes every pass unconditionally, so no
    #   collective ever sits under data-dependent control flow (GC-J107).
    #   Only the stage whose turn it is KEEPS its block outputs
    #   (jnp.where select) and writes real pages — inactive stages' KV
    #   writes are redirected to scratch page 0, exactly like masked lanes.
    # - activations hop stage -> stage on a ppermute ring between passes;
    #   the final stage's head output publishes with a select-psum (every
    #   other stage contributes zeros).
    # - the pool's LAYERS axis is sharded over pp_axis, so ``attend``'s
    #   ``layer`` argument is the stage-LOCAL block index — the model's
    #   block_* helpers are called per block with that local index.

    def _pp_stage(self, params):
        """Per-shard view of the staged params inside a shard_map body:
        ``(stage index, this stage's [layers/pp, ...] block leaves,
        shared embed/final_ln)``."""
        s = jax.lax.axis_index(self._pp_axis)
        local = jax.tree.map(lambda a: a[0], params["stages"])
        return s, local, params["shared"]

    def _pp_decode_fn(self):
        """Staged single-wave decode step: PP unrolled passes through the
        ring, each pass running this stage's blocks (kept only when it is
        the active stage). One token per slot per call — the wave tick
        (:meth:`_pp_tick_fn`) is the bubble-free schedule on top of the
        same per-stage body."""
        model, page = self.model, self.page_size
        bidx = jnp.arange(self.num_slots)
        PP, axis = self._pp, self._pp_axis
        per = int(model.num_layers) // PP
        perm = [(i, (i + 1) % PP) for i in range(PP)]

        def decode(params, k_pool, v_pool, token, pos, table, keys,
                   temp, topk):
            s, local, shared = self._pp_stage(params)
            x = model.decode_embed(shared, token, pos)
            for i in range(PP):
                if i:
                    x = jax.lax.ppermute(x, axis, perm)
                active = s == i

                def attend(layer, q, k_new, v_new, cache, p,
                           _active=active):
                    kp, vp = cache
                    pids = jnp.where(_active, table[bidx, p // page], 0)
                    off = p % page
                    kp = self._kv_rows(kp, layer, pids, off, k_new)
                    vp = self._kv_rows(vp, layer, pids, off, v_new)
                    out = self._paged_att(q, kp, vp, layer, table, p + 1)
                    return out.astype(q.dtype), (kp, vp)

                y = x
                for jl in range(per):
                    bp = jax.tree.map(lambda a, _j=jl: a[_j], local)
                    y, (k_pool, v_pool) = model.block_decode(
                        bp, y, jl, (k_pool, v_pool), pos, attend,
                        tp_axis=self._tp_axis)
                x = jnp.where(active, y, x)
            logits = model.decode_head(shared, x)
            tok, keys = self._sample_tokens(logits, keys, temp, topk)
            last = s == PP - 1
            tok = jax.lax.psum(jnp.where(last, tok, 0), axis)
            keys = jax.lax.psum(jnp.where(last, keys, 0), axis)
            return tok, k_pool, v_pool, keys

        return decode

    def _pp_prefill_fn(self, bucket: int):
        """Staged ladder prefill for one bucket: same ring schedule as
        :meth:`_pp_decode_fn`, each stage committing only its own layers'
        K/V into its layers-shard of the pool."""
        model, page = self.model, self.page_size
        npages = bucket // page
        PP, axis = self._pp, self._pp_axis
        per = int(model.num_layers) // PP
        perm = [(i, (i + 1) % PP) for i in range(PP)]

        def prefill(params, k_pool, v_pool, ids, length, page_ids):
            s, local, shared = self._pp_stage(params)
            x = model.prefill_embed(shared, ids)
            for i in range(PP):
                if i:
                    x = jax.lax.ppermute(x, axis, perm)
                active = s == i
                pids = jnp.where(active, page_ids, 0)
                y = x
                for jl in range(per):
                    bp = jax.tree.map(lambda a, _j=jl: a[_j], local)
                    y, k, v = model.block_prefill(bp, y,
                                                  tp_axis=self._tp_axis)
                    kk = jnp.transpose(k[0], (1, 0, 2)).reshape(
                        npages, page, k.shape[1], k.shape[3])
                    vv = jnp.transpose(v[0], (1, 0, 2)).reshape(
                        npages, page, v.shape[1], v.shape[3])
                    k_pool = self._kv_pages(k_pool, jl, pids, kk)
                    v_pool = self._kv_pages(v_pool, jl, pids, vv)
                x = jnp.where(active, y, x)
            logits = model.head_last(shared, x, lengths=length)
            logits = jax.lax.psum(
                jnp.where(s == PP - 1, logits, 0.0), axis)
            return logits, k_pool, v_pool

        return prefill

    def _pp_suffix_fn(self):
        """Staged suffix prefill (see :meth:`_suffix_fn` for the chunk
        semantics): the manual gather-attend runs per stage over its local
        layers, pad AND inactive-stage writes both land in scratch."""
        model, page, C = self.model, self.page_size, self._chunk_width
        maxp = self.max_pages_per_slot
        scale = 1.0 / math.sqrt(model.head_dim)
        j = jnp.arange(C, dtype=jnp.int32)
        tpos = jnp.arange(maxp * page, dtype=jnp.int32)
        PP, axis = self._pp, self._pp_axis
        per = int(model.num_layers) // PP
        perm = [(i, (i + 1) % PP) for i in range(PP)]

        def suffix_prefill(params, k_pool, v_pool, ids, start, valid, ctable):
            s, local, shared = self._pp_stage(params)
            x = model.suffix_embed(shared, ids, start)
            for i in range(PP):
                if i:
                    x = jax.lax.ppermute(x, axis, perm)
                active = s == i

                def attend(layer, q, k_new, v_new, cache, st,
                           _active=active):
                    kp, vp = cache
                    heads, hd = self._kv_heads(kp)             # local heads
                    pos_abs = st[0] + j
                    pids = ctable[jnp.clip(pos_abs // page, 0, maxp - 1)]
                    pids = jnp.where(j < valid[0], pids, 0)
                    pids = jnp.where(_active, pids, 0)
                    off = pos_abs % page
                    kc = jnp.transpose(k_new[0], (1, 0, 2))
                    vc = jnp.transpose(v_new[0], (1, 0, 2))
                    kp = self._kv_rows(kp, layer, pids, off, kc)
                    vp = self._kv_rows(vp, layer, pids, off, vc)
                    hk = self._kv_gather(kp, layer, ctable).reshape(
                        maxp * page, heads, hd)
                    hv = self._kv_gather(vp, layer, ctable).reshape(
                        maxp * page, heads, hd)
                    sc = jnp.einsum("hcd,lhd->hcl",
                                    q[0].astype(jnp.float32),
                                    hk) * scale
                    ok = tpos[None, :] <= pos_abs[:, None]
                    sc = jnp.where(ok[None, :, :], sc, -1e30)
                    pr = jax.nn.softmax(sc, axis=-1)
                    out = jnp.einsum("hcl,lhd->hcd", pr, hv)
                    return out[None].astype(q.dtype), (kp, vp)

                y = x
                for jl in range(per):
                    bp = jax.tree.map(lambda a, _j=jl: a[_j], local)
                    y, (k_pool, v_pool) = model.block_suffix(
                        bp, y, jl, (k_pool, v_pool), start, attend,
                        tp_axis=self._tp_axis)
                x = jnp.where(active, y, x)
            logits = model.head_last(shared, x, lengths=valid)
            logits = jax.lax.psum(
                jnp.where(s == PP - 1, logits, 0.0), axis)
            return logits, k_pool, v_pool

        return suffix_prefill

    def _pp_verify_fn(self):
        """Staged speculative verify (see :meth:`_verify_fn`): one ring
        traversal scoring all ``spec_k + 1`` chunk positions, greedy grid
        and bonus sample published from the final stage."""
        model, page, maxp = self.model, self.page_size, self.max_pages_per_slot
        S = self.spec_k + 1
        bidx = jnp.arange(self.num_slots)
        j = jnp.arange(S, dtype=jnp.int32)
        PP, axis = self._pp, self._pp_axis
        per = int(model.num_layers) // PP
        perm = [(i, (i + 1) % PP) for i in range(PP)]

        def verify(params, k_pool, v_pool, ids, start, nvalid, table, keys,
                   temp, topk):
            s, local, shared = self._pp_stage(params)
            x = model.suffix_embed(shared, ids, start)
            for i in range(PP):
                if i:
                    x = jax.lax.ppermute(x, axis, perm)
                active = s == i

                def attend(layer, q, k_new, v_new, cache, st,
                           _active=active):
                    kp, vp = cache
                    pos_abs = st[:, None] + j[None, :]
                    pids = table[bidx[:, None],
                                 jnp.clip(pos_abs // page, 0, maxp - 1)]
                    pids = jnp.where(j[None, :] < nvalid[:, None], pids, 0)
                    pids = jnp.where(_active, pids, 0)
                    off = pos_abs % page
                    kc = jnp.transpose(k_new, (0, 2, 1, 3))
                    vc = jnp.transpose(v_new, (0, 2, 1, 3))
                    kp = self._kv_rows(kp, layer, pids, off, kc)
                    vp = self._kv_rows(vp, layer, pids, off, vc)
                    out = self._paged_verify_att(q, kp, vp, layer, table, st)
                    return out.astype(q.dtype), (kp, vp)

                y = x
                for jl in range(per):
                    bp = jax.tree.map(lambda a, _j=jl: a[_j], local)
                    y, (k_pool, v_pool) = model.block_suffix(
                        bp, y, jl, (k_pool, v_pool), start, attend,
                        tp_axis=self._tp_axis)
                x = jnp.where(active, y, x)
            logits = model.head_all(shared, x)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            samp0, keys = self._sample_tokens(logits[:, 0], keys, temp, topk)
            last = s == PP - 1
            g = jax.lax.psum(jnp.where(last, g, 0), axis)
            samp0 = jax.lax.psum(jnp.where(last, samp0, 0), axis)
            keys = jax.lax.psum(jnp.where(last, keys, 0), axis)
            return g, samp0, k_pool, v_pool, keys

        return verify

    def _pp_self_draft_fn(self):
        """Staged self-speculation chain: ``draft_layers`` spans the first
        ``draft_layers / (layers/pp)`` stages (validated at construction),
        so each of the ``spec_k`` greedy steps traverses only that ring
        prefix and the drafted token broadcasts back to every stage with a
        select-psum before the next step embeds it."""
        model, page, maxp = self.model, self.page_size, self.max_pages_per_slot
        K, Ld = self.spec_k, self.draft_layers
        bidx = jnp.arange(self.num_slots)
        PP, axis = self._pp, self._pp_axis
        per = int(model.num_layers) // PP
        ds = Ld // per                      # stages the draft spans
        perm = [(i, (i + 1) % PP) for i in range(PP)]

        def draft(params, k_pool, v_pool, token, pos, table, nappend):
            s, local, shared = self._pp_stage(params)
            writable = pos + nappend        # first position with no room

            toks, tok = [], token
            for jk in range(K):
                p = pos + jk
                x = model.decode_embed(shared, tok, p)
                for i in range(ds):
                    if i:
                        x = jax.lax.ppermute(x, axis, perm)
                    active = s == i

                    def attend(layer, q, k_new, v_new, cache, pq,
                               _active=active):
                        kp, vp = cache
                        pids = table[bidx,
                                     jnp.clip(pq // page, 0, maxp - 1)]
                        pids = jnp.where(pq < writable, pids, 0)
                        pids = jnp.where(_active, pids, 0)
                        off = pq % page
                        kp = self._kv_rows(kp, layer, pids, off, k_new)
                        vp = self._kv_rows(vp, layer, pids, off, v_new)
                        out = self._paged_att(q, kp, vp, layer, table,
                                              pq + 1)
                        return out.astype(q.dtype), (kp, vp)

                    y = x
                    for jl in range(per):
                        bp = jax.tree.map(lambda a, _j=jl: a[_j], local)
                        y, (k_pool, v_pool) = model.block_decode(
                            bp, y, jl, (k_pool, v_pool), p, attend,
                            tp_axis=self._tp_axis)
                    x = jnp.where(active, y, x)
                logits = model.decode_head(shared, x)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                tok = jax.lax.psum(jnp.where(s == ds - 1, tok, 0), axis)
                toks.append(tok)
            return jnp.stack(toks, axis=1), k_pool, v_pool

        return draft

    def _pp_tick_fn(self):
        """Micro-token wave tick: ONE pass per stage per call, every stage
        busy on its OWN wave. At tick t stage s runs wave ``(t - s) mod pp``
        — stage 0 embeds the entry wave's freshly appended tokens, every
        other stage continues the activations that hopped in on the carry
        ring last tick, and the final stage samples the exit wave. Wall
        clock per tick is ~1/pp of the flat step, so a full pipeline emits
        the same tokens/sec with no stage ever idle (bubble only at
        drain/refill edges). One fixed-shape executable — tick index, wave
        operands and the carry are all traced operands."""
        model, page = self.model, self.page_size
        PP, axis = self._pp, self._pp_axis
        per = int(model.num_layers) // PP
        W = self.num_slots // PP
        widx = jnp.arange(W)
        perm = [(i, (i + 1) % PP) for i in range(PP)]

        def tick(params, k_pool, v_pool, x_carry, t, token, pos, table,
                 keys, temp, topk):
            s, local, shared = self._pp_stage(params)
            w = jnp.mod(t - s, PP)
            o = w * W
            tok_w = jax.lax.dynamic_slice_in_dim(token, o, W)
            pos_w = jax.lax.dynamic_slice_in_dim(pos, o, W)
            tab_w = jax.lax.dynamic_slice_in_dim(table, o, W, axis=0)
            key_w = jax.lax.dynamic_slice_in_dim(keys, o, W, axis=0)
            tmp_w = jax.lax.dynamic_slice_in_dim(temp, o, W)
            tpk_w = jax.lax.dynamic_slice_in_dim(topk, o, W)
            # stage 0 ingests its wave at the embed; later stages pick up
            # where the carry ring left their wave last tick
            x = jnp.where(s == 0,
                          model.decode_embed(shared, tok_w, pos_w),
                          x_carry[0])

            def attend(layer, q, k_new, v_new, cache, p):
                kp, vp = cache
                pids = tab_w[widx, p // page]
                off = p % page
                kp = self._kv_rows(kp, layer, pids, off, k_new)
                vp = self._kv_rows(vp, layer, pids, off, v_new)
                out = self._paged_att(q, kp, vp, layer, tab_w, p + 1)
                return out.astype(q.dtype), (kp, vp)

            for jl in range(per):
                bp = jax.tree.map(lambda a, _j=jl: a[_j], local)
                x, (k_pool, v_pool) = model.block_decode(
                    bp, x, jl, (k_pool, v_pool), pos_w, attend,
                    tp_axis=self._tp_axis)
            logits = model.decode_head(shared, x)
            tok, key = self._sample_tokens(logits, key_w, tmp_w, tpk_w)
            last = s == PP - 1
            tok = jax.lax.psum(jnp.where(last, tok, 0), axis)
            key = jax.lax.psum(jnp.where(last, key, 0), axis)
            x_next = jax.lax.ppermute(x, axis, perm)
            return tok, key, k_pool, v_pool, x_next[None]

        return tick

    def _param_struct(self):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
            if not hasattr(a, "aval")
            else jax.ShapeDtypeStruct(a.shape, a.dtype), self._params)

    def _pool_struct(self):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self._k_pool)

    def _aot_locked(self, fn, donate, arg_structs, specs=None,
                    out_specs=None, key=None):
        """jit -> lower -> compile one decode-plane executable. With model
        parallelism on (and ``specs`` given), the body wraps in a shard_map
        over the serving mesh — pallas custom calls have no GSPMD
        partitioning rule, so every executable is explicitly per-shard with
        replicated activations — and the inputs carry matching
        NamedShardings. ``tp * ep == 1`` compiles the exact unwrapped
        program.

        With ``key`` and an executable store configured, the store is the
        first tier — a deserialized executable skips tracing and XLA
        entirely (zero-compile cold start) — and anything compiled here is
        queued for save-back (flushed by ``warmup`` after the engine lock
        is released)."""
        if key is not None and self.exec_store is not None:
            exe = self.exec_store.load(key)
            if exe is not None:
                self.serialized_loads += 1
                return exe
        guard = self.recompile_guard
        if not (self._sharded and specs is not None):
            jitted = jax.jit(guard.wrap(fn), donate_argnums=donate)
        else:
            body = jax.shard_map(fn, mesh=self.mesh, in_specs=specs,
                                 out_specs=out_specs, check_vma=False)
            in_sh = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                 specs, is_leaf=lambda x: isinstance(x, P))
            jitted = jax.jit(guard.wrap(body), in_shardings=in_sh,
                             donate_argnums=donate)
        with record_attention_paths() as paths:
            exe = jitted.lower(*arg_structs).compile()
        if key is not None:
            # which kernel (or reference) each attention call of this
            # executable traced — a deserialized executable traces nothing
            # and so reports nothing
            self._attention_paths[key.rsplit("/", 1)[-1]] = sorted(set(paths))
        if key is not None and self.exec_store is not None:
            self._pending_exec_saves.append((key, exe))
        return exe

    def warmup(self) -> None:
        """AOT-compile the decode step, the prefill-sampling helper, and
        every prefill bucket, then pin steady state: any later trace is a
        recompile regression (GC-R401)."""
        with self._lock:
            self._warmup_locked()
            pending, self._pending_exec_saves = self._pending_exec_saves, []
        # save-back AFTER the lock: ExecutableStore.save waits on the
        # cross-process manifest lock, and that wait must not stall
        # threads contending the engine lock (GC-L305)
        saved = sum(1 for key, exe in pending
                    if self.exec_store.save(key, exe))
        if saved:
            with self._lock:
                self.serialized_saves += saved

    def _kv_quant_error_probe_locked(self) -> None:
        """Warmup-time error sample for the ``decode/kv_quant_error`` gauge:
        forward one synthetic page-length prompt eagerly, commit its K/V to
        a tiny throwaway pool twice (bf16-reference and quantized layouts),
        run one decode-attend through each, and record the max abs logit
        delta. Hermetic — real pools, executables and the RecompileGuard
        are untouched; any failure degrades to gauge-absent, never to a
        failed warmup."""
        try:
            model, page = self.model, self.page_size
            store_dtype, _ = quant.kv_pool_dtype(self.kv_quant)
            ref_dt = (model.compute_dtype if model.compute_dtype is not None
                      else jnp.float32)
            n = page
            rng = np.random.default_rng(0)
            ids = jnp.asarray(
                rng.integers(0, model.vocab_size, (1, n)), jnp.int32)
            logits, kvs = model.prefill(self._params, ids,
                                        lengths=jnp.asarray([n], jnp.int32))
            L = len(kvs)
            h, d = kvs[0][0].shape[1], kvs[0][0].shape[3]
            kr = jnp.zeros((L, 3, page, h, d), ref_dt)
            vr = jnp.zeros((L, 3, page, h, d), ref_dt)
            kq = (jnp.zeros((L, 3, page, h, d), store_dtype),
                  jnp.zeros((L, 3, h), jnp.float32))
            vq = (jnp.zeros((L, 3, page, h, d), store_dtype),
                  jnp.zeros((L, 3, h), jnp.float32))
            pid = jnp.asarray([1], jnp.int32)
            for i, (k, v) in enumerate(kvs):
                kk = jnp.transpose(k[0], (1, 0, 2))[None]  # [1, page, h, d]
                vv = jnp.transpose(v[0], (1, 0, 2))[None]
                kr = kr.at[i, pid].set(kk.astype(ref_dt))
                vr = vr.at[i, pid].set(vv.astype(ref_dt))
                kq = quant.paged_quant_write_pages(kq[0], kq[1], i, pid, kk)
                vq = quant.paged_quant_write_pages(vq[0], vq[1], i, pid, vv)
            table = jnp.asarray([[1, 2]], jnp.int32)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            pos = jnp.asarray([n], jnp.int32)
            bidx = jnp.arange(1)

            def attend_ref(layer, q, k_new, v_new, cache, p):
                kp, vp = cache
                pids, off = table[bidx, p // page], p % page
                kp = kp.at[layer, pids, off].set(k_new.astype(kp.dtype))
                vp = vp.at[layer, pids, off].set(v_new.astype(vp.dtype))
                out = paged_attention(q, kp[layer], vp[layer], table, p + 1)
                return out.astype(q.dtype), (kp, vp)

            def attend_q(layer, q, k_new, v_new, cache, p):
                kp, vp = cache
                pids, off = table[bidx, p // page], p % page
                kp = quant.paged_quant_append(kp[0], kp[1], layer, pids,
                                              off, k_new)
                vp = quant.paged_quant_append(vp[0], vp[1], layer, pids,
                                              off, v_new)
                out = paged_attention(q, kp[0][layer], vp[0][layer], table,
                                      p + 1, k_scales=kp[1][layer],
                                      v_scales=vp[1][layer])
                return out.astype(q.dtype), (kp, vp)

            lg_ref, _ = model.decode_step(self._params, (kr, vr), tok, pos,
                                          attend=attend_ref)
            lg_q, _ = model.decode_step(self._params, (kq, vq), tok, pos,
                                        attend=attend_q)
            err = float(jnp.max(jnp.abs(
                lg_q.astype(jnp.float32) - lg_ref.astype(jnp.float32))))
            self._kv_quant_error = err
            self.metrics.gauge("decode/kv_quant_error", err)
        except Exception:  # pragma: no cover - diagnostics only
            self._kv_quant_error = None

    def _warmup_locked(self) -> None:
        guard = self.recompile_guard
        ps = self._param_struct()
        pool = self._pool_struct()
        B, maxp = self.num_slots, self.max_pages_per_slot
        i32 = jnp.int32
        psp, pls, R = self._param_specs, self._pool_spec, P()
        if self._decode_exe is None:
            with annotate("serving/decode_compile_step"):
                self._decode_exe = self._aot_locked(
                    self._decode_fn, (1, 2),
                    (ps, pool, pool,
                     jax.ShapeDtypeStruct((B,), i32),
                     jax.ShapeDtypeStruct((B,), i32),
                     jax.ShapeDtypeStruct((B, maxp), i32),
                     jax.ShapeDtypeStruct((B, 2), jnp.uint32),
                     jax.ShapeDtypeStruct((B,), jnp.float32),
                     jax.ShapeDtypeStruct((B,), i32)),
                    specs=(psp, pls, pls, R, R, R, R, R, R),
                    out_specs=(R, pls, pls, R),
                    key=f"{self._exec_prefix}/step")
            self.aot_compiles += 1
        if self._sample_exe is None:
            with annotate("serving/decode_compile_sample"):
                self._sample_exe = self._aot_locked(
                    self._sample_tokens, (),
                    (jax.ShapeDtypeStruct((1, self.model.vocab_size),
                                          jnp.float32),
                     jax.ShapeDtypeStruct((1, 2), jnp.uint32),
                     jax.ShapeDtypeStruct((1,), jnp.float32),
                     jax.ShapeDtypeStruct((1,), i32)),
                    specs=(R, R, R, R),
                    out_specs=(R, R),
                    key=f"{self._exec_prefix}/sample")
            self.aot_compiles += 1
        for b in self.prefill_buckets:
            if b in self._prefill_exes:
                continue
            with annotate(f"serving/decode_compile_prefill_b{b}"):
                self._prefill_exes[b] = self._aot_locked(
                    self._prefill_fn(b), (1, 2),
                    (ps, pool, pool,
                     jax.ShapeDtypeStruct((1, b), i32),
                     jax.ShapeDtypeStruct((1,), i32),
                     jax.ShapeDtypeStruct((b // self.page_size,), i32)),
                    specs=(psp, pls, pls, R, R, R),
                    out_specs=(R, pls, pls),
                    key=f"{self._exec_prefix}/prefill_b{b}")
            self.aot_compiles += 1
        C = self._chunk_width
        chunk_structs = (
            jax.ShapeDtypeStruct((1, C), i32),       # ids
            jax.ShapeDtypeStruct((1,), i32),         # start
            jax.ShapeDtypeStruct((1,), i32),         # valid
            jax.ShapeDtypeStruct((maxp,), i32))      # slot's table row
        if self._suffix_exe is None:
            with annotate("serving/decode_compile_suffix"):
                self._suffix_exe = self._aot_locked(
                    self._suffix_fn(), (1, 2),
                    (ps, pool, pool, *chunk_structs),
                    specs=(psp, pls, pls, R, R, R, R),
                    out_specs=(R, pls, pls),
                    key=f"{self._exec_prefix}/suffix")
            self.aot_compiles += 1
        if self.prefill_chunk and self._fused_exe is None:
            with annotate("serving/decode_compile_fused"):
                self._fused_exe = self._aot_locked(
                    self._fused_fn(), (1, 2),
                    (ps, pool, pool, *chunk_structs,
                     jax.ShapeDtypeStruct((B,), i32),
                     jax.ShapeDtypeStruct((B,), i32),
                     jax.ShapeDtypeStruct((B, maxp), i32),
                     jax.ShapeDtypeStruct((B, 2), jnp.uint32),
                     jax.ShapeDtypeStruct((B,), jnp.float32),
                     jax.ShapeDtypeStruct((B,), i32)),
                    specs=(psp, pls, pls, R, R, R, R, R, R, R, R, R, R),
                    out_specs=(R, R, pls, pls, R),
                    key=f"{self._exec_prefix}/fused")
            self.aot_compiles += 1
        if self._pp_wave and self._tick_exe is None:
            xc = jax.ShapeDtypeStruct(self._x_carry.shape,
                                      self._x_carry.dtype)
            pcar = P(self._pp_axis)
            with annotate("serving/decode_compile_wave_tick"):
                self._tick_exe = self._aot_locked(
                    self._pp_tick_fn(), (1, 2, 3),
                    (ps, pool, pool, xc,
                     jax.ShapeDtypeStruct((), i32),
                     jax.ShapeDtypeStruct((B,), i32),
                     jax.ShapeDtypeStruct((B,), i32),
                     jax.ShapeDtypeStruct((B, maxp), i32),
                     jax.ShapeDtypeStruct((B, 2), jnp.uint32),
                     jax.ShapeDtypeStruct((B,), jnp.float32),
                     jax.ShapeDtypeStruct((B,), i32)),
                    specs=(psp, pls, pls, pcar, R, R, R, R, R, R, R),
                    out_specs=(R, R, pls, pls, pcar),
                    key=f"{self._exec_prefix}/wave_tick")
            self.aot_compiles += 1
        if self.spec_k:
            self._warmup_spec_locked(ps, pool, B, maxp)
        if self._quantized and self._kv_quant_error is None \
                and not self._sharded:
            self._kv_quant_error_probe_locked()
        guard.mark_steady()

    def _warmup_spec_locked(self, ps, pool, B: int, maxp: int) -> None:
        guard = self.recompile_guard
        i32 = jnp.int32
        S = self.spec_k + 1
        psp, pls, R = self._param_specs, self._pool_spec, P()
        if self._verify_exe is None:
            with annotate("serving/decode_compile_verify"):
                self._verify_exe = self._aot_locked(
                    self._verify_fn(), (1, 2),
                    (ps, pool, pool,
                     jax.ShapeDtypeStruct((B, S), i32),      # chunk ids
                     jax.ShapeDtypeStruct((B,), i32),        # start
                     jax.ShapeDtypeStruct((B,), i32),        # nvalid
                     jax.ShapeDtypeStruct((B, maxp), i32),
                     jax.ShapeDtypeStruct((B, 2), jnp.uint32),
                     jax.ShapeDtypeStruct((B,), jnp.float32),
                     jax.ShapeDtypeStruct((B,), i32)),
                    specs=(psp, pls, pls, R, R, R, R, R, R, R),
                    out_specs=(R, R, pls, pls, R),
                    key=f"{self._exec_prefix}/verify")
            self.aot_compiles += 1
        if self._copy_exe is None:
            with annotate("serving/decode_compile_copy"):
                self._copy_exe = self._aot_locked(
                    self._copy_pages_fn, (0, 1),
                    (pool, pool,
                     jax.ShapeDtypeStruct((), i32),
                     jax.ShapeDtypeStruct((), i32)),
                    specs=(pls, pls, R, R),
                    out_specs=(pls, pls),
                    key=f"{self._exec_prefix}/copy")
            self.aot_compiles += 1
        if self._draft_model is None:
            if self._draft_exe is None:
                with annotate("serving/decode_compile_draft"):
                    self._draft_exe = self._aot_locked(
                        self._self_draft_fn(), (1, 2),
                        (ps, pool, pool,
                         jax.ShapeDtypeStruct((B,), i32),    # token
                         jax.ShapeDtypeStruct((B,), i32),    # pos
                         jax.ShapeDtypeStruct((B, maxp), i32),
                         jax.ShapeDtypeStruct((B,), i32)),   # nappend
                        specs=(psp, pls, pls, R, R, R, R),
                        out_specs=(R, pls, pls),
                        key=f"{self._exec_prefix}/draft")
                self.aot_compiles += 1
            return
        dps = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
            if not hasattr(a, "aval")
            else jax.ShapeDtypeStruct(a.shape, a.dtype), self._draft_params)
        dpool = jax.ShapeDtypeStruct(self._draft_k.shape,
                                     self._draft_k.dtype)
        if self._draft_exe is None:
            with annotate("serving/decode_compile_draft"):
                self._draft_exe = jax.jit(
                    guard.wrap(self._ext_draft_fn()),
                    donate_argnums=(1, 2)).lower(
                        dps, dpool, dpool,
                        jax.ShapeDtypeStruct((B,), i32),        # token
                        jax.ShapeDtypeStruct((B,), i32),        # pos
                        jax.ShapeDtypeStruct((B,), jnp.bool_)   # live
                        ).compile()
            self.aot_compiles += 1
        for b in self.prefill_buckets:
            if b in self._draft_prefill_exes:
                continue
            with annotate(f"serving/decode_compile_draft_prefill_b{b}"):
                self._draft_prefill_exes[b] = jax.jit(
                    guard.wrap(self._ext_draft_prefill_fn(b)),
                    donate_argnums=(1, 2)).lower(
                        dps, dpool, dpool,
                        jax.ShapeDtypeStruct((1, b), i32),
                        jax.ShapeDtypeStruct((1,), i32),
                        jax.ShapeDtypeStruct((), i32)).compile()
            self.aot_compiles += 1

    # -- admission / prefill -------------------------------------------------

    def can_admit(self, prompt_len: int, max_new_tokens: int,
                  prompt: Optional[Sequence[int]] = None) -> bool:
        """Token-boundary admission check: a free slot exists and the pool
        can reserve the request's worst case. With the actual ``prompt``
        tokens (and prefix caching on), indexed prefix pages are subtracted
        from the demand — the exact mirror of :meth:`prefill`'s alloc."""
        if not (1 <= prompt_len <= self.max_prompt_len):
            return False
        total = prompt_len + max(1, int(max_new_tokens))
        if total > self.max_seq_len:
            return False
        with self._lock:
            if (self._pending_swap is not None
                    and not self._maybe_swap_locked()):
                # a prepared weight swap is waiting for the drained boundary;
                # hold new admissions so it lands (callers queue, no failures)
                return False
        return self.kv.can_admit(
            total, list(prompt) if (prompt is not None
                                    and self.prefix_cache) else None)

    def prefill(self, prompt: Sequence[int], *, max_new_tokens: int = 32,
                temperature: float = 0.0, top_k: int = 0,
                seed: Optional[int] = None) -> Dict[str, Any]:
        """Admit one sequence: allocate a slot + pages (mapping any indexed
        shared prefix straight into the table), prefill what isn't shared —
        the bucketed ladder for cold prompts, the suffix executable for
        prefix hits — and sample the first token. With chunked prefill
        enabled and a suffix longer than ``prefill_chunk``, the call returns
        immediately with ``token=None``; the suffix advances one chunk per
        :meth:`step` and the first token surfaces there.

        Returns ``{"slot", "token", "prompt_len", "shared_tokens",
        "chunked"}``; raises
        :class:`~sparkflow_tpu.serving.kvcache.OutOfPages` when the request
        cannot be admitted right now (backpressure)."""
        prompt = list(int(t) for t in prompt)
        n = len(prompt)
        if not 1 <= n <= self.max_prompt_len:
            raise ValueError(f"prompt length {n} outside [1, "
                             f"{self.max_prompt_len}]")
        total = n + max(1, int(max_new_tokens))
        if total > self.max_seq_len:
            raise ValueError(f"prompt + max_new_tokens = {total} exceeds "
                             f"max_seq_len={self.max_seq_len}")
        with self._lock:
            if (self._pending_swap is not None
                    and not self._maybe_swap_locked()):
                # backpressure, not failure: the batcher requeues and the
                # swap lands once the active slots drain
                raise OutOfPages("weight swap pending at token boundary")
            slot = self.kv.free_slot()
            if slot is None:
                raise OutOfPages("no free decode slot")
            shared_pages, _saved = self.kv.alloc(
                slot, prompt if self.prefix_cache else n, total)
            try:
                t0 = time.perf_counter()
                start = shared_pages * self.page_size  # first un-shared pos
                self._temp[slot] = float(temperature)
                self._topk[slot] = min(int(top_k), self.max_top_k)
                self._decode_ready[slot] = False
                if seed is not None:
                    self._keys[slot] = np.asarray(
                        jax.random.PRNGKey(int(seed)))
                self._prefills += 1
                self.metrics.observe("serving/decode/prompt_tokens", n)
                if (self.prefill_chunk is not None
                        and n - start > self.prefill_chunk):
                    # chunked admission: the suffix rides the decode loop,
                    # one fused chunk per step; nothing blocks here
                    self._pending.append({"slot": int(slot),
                                          "prompt": prompt,
                                          "next": start, "end": n,
                                          "seed": seed, "t0": t0})
                    return {"slot": int(slot), "token": None,
                            "prompt_len": n, "shared_tokens": start,
                            "chunked": True}
                if start == 0:
                    bucket = next(b for b in self.prefill_buckets if n <= b)
                    ids = np.zeros((1, bucket), np.int32)
                    ids[0, :n] = prompt
                    npages = bucket // self.page_size
                    page_ids = np.zeros(npages, np.int32)  # pad -> page 0
                    held = self.kv.pages_for(n, self.page_size)
                    page_ids[:held] = self.kv.page_tables()[slot, :held]
                    exe = self._prefill_exes[bucket]
                    with obs_span("serving/decode_prefill",
                                  args={"bucket": bucket, "slot": int(slot)},
                                  jax_annotation=True):
                        logits, self._k_pool, self._v_pool = exe(
                            self._params, self._k_pool, self._v_pool, ids,
                            np.asarray([n], np.int32), page_ids)
                else:
                    logits = self._suffix_prefill_locked(slot, prompt,
                                                         start, n)
                if self.prefix_cache:
                    self.kv.commit_prefix(slot, prompt)  # K/V on device now
                if self._draft_model is not None:
                    # the draft keeps its own cache, so prefix hits on the
                    # target side still need a full draft prefill
                    self._draft_prefill_locked(slot, prompt)
                tok, key = self._sample_exe(
                    np.asarray(logits), self._keys[slot][None],
                    np.asarray([temperature], np.float32),
                    np.asarray([min(int(top_k), self.max_top_k)], np.int32))
                self._keys[slot] = np.asarray(key)[0]
                first = int(np.asarray(tok)[0])
                self._last_token[slot] = first
                self._decode_ready[slot] = True
                self.metrics.observe("serving/decode/prefill_ms",
                                     (time.perf_counter() - t0) * 1000.0)
            except BaseException:
                # a prefill that dies after alloc (OOM mid-executable, XLA
                # error) must hand the slot's pages back before the error
                # propagates — the caller never learns the slot id, so
                # nobody else can release it
                self._release_locked(int(slot))
                raise
        return {"slot": int(slot), "token": first, "prompt_len": n,
                "shared_tokens": start, "chunked": False}

    def _suffix_prefill_locked(self, slot: int, prompt: List[int],
                               start: int, n: int):
        """Synchronous suffix prefill for a prefix-hit prompt: forward
        ``prompt[start:]`` through the fixed-shape suffix executable in
        ``_chunk_width`` pieces. Returns the final chunk's logits."""
        C = self._chunk_width
        row = self.kv.page_tables()[slot]
        logits = None
        p = start
        while p < n:
            c = min(C, n - p)
            ids = np.zeros((1, C), np.int32)
            ids[0, :c] = prompt[p:p + c]
            with obs_span("serving/decode_prefill_suffix",
                          args={"slot": int(slot), "start": int(p)},
                          jax_annotation=True):
                logits, self._k_pool, self._v_pool = self._suffix_exe(
                    self._params, self._k_pool, self._v_pool, ids,
                    np.asarray([p], np.int32), np.asarray([c], np.int32),
                    row)
            p += c
        return logits

    def _draft_prefill_locked(self, slot: int, prompt: List[int]) -> None:
        """Fill the external draft's dense cache lane for ``slot`` through
        its bucket ladder (one bucket call — the draft is small)."""
        n = len(prompt)
        bucket = next(b for b in self.prefill_buckets if n <= b)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = prompt
        with obs_span("serving/decode_draft_prefill",
                      args={"bucket": bucket, "slot": int(slot)},
                      jax_annotation=True):
            self._draft_k, self._draft_v = self._draft_prefill_exes[bucket](
                self._draft_params, self._draft_k, self._draft_v, ids,
                np.asarray([n], np.int32), np.int32(slot))

    # -- decode --------------------------------------------------------------

    def step(self) -> Dict[int, List[int]]:
        """One decode iteration over every decode-ready slot: append page
        room, run the fixed-shape step, return ``{slot: [tokens...]}`` — a
        burst of 1 token per slot normally, up to ``spec_k + 1`` with
        speculation on (the accepted draft prefix plus the target's bonus
        token, in order). Pending chunked prefills advance one chunk here,
        fused into the same device call; a slot whose final chunk just
        committed contributes its *first* token to the result. While a
        chunk is pending the speculative path stands down for the iteration
        (plain fused step) so the chunk work stays fused with decode. No-op
        (empty dict) when nothing is active.

        With wave scheduling on (``pp_wave`` under a pp mesh) each call is
        one pipeline *tick*: roughly ``1/pp`` of the slots emit a token per
        call and a slot's next token arrives ``pp`` ticks after its entry —
        same steady-state tokens/sec, every stage busy. Pending chunked
        prefills drain the pipeline first, then run the flat fused call."""
        with self._lock:
            if self._pending_swap is not None:
                self._maybe_swap_locked()  # lands iff fully drained
            active = self.kv.active_slots()
            ready = np.asarray([int(s) for s in active
                                if self._decode_ready[s]], np.int64)
            state = self._pending[0] if self._pending else None
            if self._pp_wave and state is None:
                return self._wave_step_locked(ready)
            if ready.size == 0 and state is None:
                return {}
            if self.spec_k and state is None:
                return self._spec_step_locked(ready)
            t0 = time.perf_counter()
            pre: Dict[int, List[int]] = {}
            if self._pp_wave:
                # the fused chunk call runs the flat (single-wave) staged
                # schedule: quiesce the wave pipeline first so every
                # in-flight token lands before new page room is appended
                pre = self._drain_waves_locked()
                ready = np.asarray(
                    [int(s) for s in self.kv.active_slots()
                     if self._decode_ready[s]], np.int64)
            # the incoming token occupies position == current length: make
            # sure its page exists, then pass the PRE-append position
            for s in ready:
                self.kv.append(int(s))
            lengths = self.kv.lengths()
            table_full = self.kv.page_tables()
            # mask non-ready lanes (mid-chunked-prefill or idle) to scratch:
            # the fixed-shape step must not write into half-committed pages
            mask = np.zeros(self.num_slots, bool)
            mask[ready] = True
            pos = np.maximum(lengths - 1, 0).astype(np.int32)
            pos[~mask] = 0
            table = table_full.copy()
            table[~mask] = 0
            token = np.where(mask, self._last_token, 0).astype(np.int32)
            out: Dict[int, List[int]] = {}
            if state is not None:
                C = self._chunk_width
                p, end = state["next"], state["end"]
                c = min(C, end - p)
                ids = np.zeros((1, C), np.int32)
                ids[0, :c] = state["prompt"][p:p + c]
                with obs_span("serving/decode_fused_step",
                              args={"active": int(ready.size),
                                    "slot": state["slot"]},
                              jax_annotation=True):
                    logits, tok, self._k_pool, self._v_pool, keys = \
                        self._fused_exe(
                            self._params, self._k_pool, self._v_pool, ids,
                            np.asarray([p], np.int32),
                            np.asarray([c], np.int32),
                            table_full[state["slot"]], token, pos, table,
                            self._keys, self._temp, self._topk)
                self._keys = np.array(keys)
                state["next"] = p + c
                if state["next"] >= end:  # final chunk: first token is born
                    self._pending.pop(0)
                    slot = state["slot"]
                    if self.prefix_cache:
                        self.kv.commit_prefix(slot, state["prompt"])
                    if self._draft_model is not None:
                        self._draft_prefill_locked(slot, state["prompt"])
                    if state["seed"] is not None:
                        # the fused steps advanced every lane's key; re-pin
                        # the requested seed before the first sample
                        self._keys[slot] = np.asarray(
                            jax.random.PRNGKey(int(state["seed"])))
                    ftok, key = self._sample_exe(
                        np.asarray(logits), self._keys[slot][None],
                        np.asarray([self._temp[slot]], np.float32),
                        np.asarray([self._topk[slot]], np.int32))
                    self._keys[slot] = np.asarray(key)[0]
                    first = int(np.asarray(ftok)[0])
                    self._last_token[slot] = first
                    self._decode_ready[slot] = True
                    out[int(slot)] = [first]
                    self.metrics.observe(
                        "serving/decode/prefill_ms",
                        (time.perf_counter() - state["t0"]) * 1000.0)
            else:
                with obs_span("serving/decode_step",
                              args={"active": int(ready.size)},
                              jax_annotation=True):
                    tok, self._k_pool, self._v_pool, keys = \
                        self._decode_exe(self._params, self._k_pool,
                                         self._v_pool, token, pos,
                                         table, self._keys, self._temp,
                                         self._topk)
                self._keys = np.array(keys)
            tok = np.asarray(tok)
            for s in ready:
                self._last_token[s] = tok[s]
                out[int(s)] = [int(tok[s])]
            self._steps += 1
            self._tokens_out += len(out)
            dt_ms = (time.perf_counter() - t0) * 1000.0
            self.metrics.observe("serving/decode/step_ms", dt_ms)
            self.metrics.observe("serving/decode/step_active",
                                 int(ready.size))
            self.metrics.observe("serving/decode/token_latency_ms",
                                 dt_ms)  # per-token: one step = one token
            if pre:
                # tokens harvested while draining the wave pipeline precede
                # this step's token for the same slot
                for sl, ts in pre.items():
                    out[sl] = ts + out.get(sl, [])
        return out

    def _wave_step_locked(self, ready: np.ndarray) -> Dict[int, List[int]]:
        """One wave tick: admit this tick's entry wave (append page room for
        its ready slots), run the staged tick executable — every stage busy
        on its own wave — and harvest the exit wave. A slot's wave is fixed
        by its lane index (``slot // (num_slots/pp)``), so a freshly
        admitted slot waits at most ``pp - 1`` ticks for its entry turn."""
        inflight = any(self._wave_inflight[w] for w in range(self._pp))
        if ready.size == 0 and not inflight:
            return {}
        t0 = time.perf_counter()
        W = self.num_slots // self._pp
        wn = self._tick % self._pp
        entry = [int(s) for s in ready if wn * W <= int(s) < (wn + 1) * W]
        for s in entry:
            self.kv.append(s)
        self._wave_inflight[wn] = entry
        out = self._run_tick_locked()
        self._steps += 1
        dt_ms = (time.perf_counter() - t0) * 1000.0
        self.metrics.observe("serving/decode/step_ms", dt_ms)
        self.metrics.observe("serving/decode/step_active", int(ready.size))
        for _ in out:
            self.metrics.observe("serving/decode/token_latency_ms", dt_ms)
        return out

    def _run_tick_locked(self) -> Dict[int, List[int]]:
        """Run one tick of the staged wave executable over the current
        in-flight waves and harvest the exiting one. Operand rebuild is
        safe mid-flight: a slot's length/table/token only change at its own
        entry tick (append) or harvest (sample), never in between."""
        B = self.num_slots
        inflight = sorted({s for lst in self._wave_inflight.values()
                           for s in lst})
        mask = np.zeros(B, bool)
        mask[inflight] = True
        lengths = self.kv.lengths()
        table_full = self.kv.page_tables()
        pos = np.maximum(lengths - 1, 0).astype(np.int32)
        pos[~mask] = 0
        table = table_full.copy()
        table[~mask] = 0
        token = np.where(mask, self._last_token, 0).astype(np.int32)
        with obs_span("serving/decode_wave_tick",
                      args={"tick": int(self._tick),
                            "inflight": len(inflight)},
                      jax_annotation=True):
            tok, keys, self._k_pool, self._v_pool, self._x_carry = \
                self._tick_exe(self._params, self._k_pool, self._v_pool,
                               self._x_carry,
                               np.int32(self._tick % self._pp), token, pos,
                               table, self._keys, self._temp, self._topk)
        we = (self._tick - (self._pp - 1)) % self._pp
        self._tick += 1
        exit_slots = self._wave_inflight[we]
        self._wave_inflight[we] = []
        out: Dict[int, List[int]] = {}
        if exit_slots:
            tok = np.asarray(tok)
            keys = np.asarray(keys)
            W = self.num_slots // self._pp
            for s in exit_slots:
                r = s - we * W
                self._last_token[s] = tok[r]
                self._keys[s] = keys[r]
                out[s] = [int(tok[r])]
            self._tokens_out += len(exit_slots)
        return out

    def _drain_waves_locked(self) -> Dict[int, List[int]]:
        """Tick the pipeline with no new entries until every in-flight wave
        has harvested (at most ``pp - 1`` ticks)."""
        out: Dict[int, List[int]] = {}
        while any(self._wave_inflight[w] for w in range(self._pp)):
            for s, ts in self._run_tick_locked().items():
                out.setdefault(s, []).extend(ts)
        return out

    def _spec_step_locked(self, ready: np.ndarray) -> Dict[int, List[int]]:
        """One speculative iteration: clamp each slot's window to its page
        room (temperature slots to 0), append the whole window's room, run
        the draft chain then the single verify call, commit the longest
        matching prefix + bonus per slot, and roll the rest back via
        :meth:`PagedKVCache.truncate`."""
        t0 = time.perf_counter()
        K = self.spec_k
        B = self.num_slots
        lengths0 = self.kv.lengths()
        rooms = self.kv.token_rooms()
        mask = np.zeros(B, bool)
        mask[ready] = True
        kb = np.zeros(B, np.int32)
        for s in ready:
            want = K if self._temp[s] == 0.0 else 0
            kb[s] = max(0, min(want, int(rooms[s]) - 1))
        nappend = np.where(mask, kb + 1, 0).astype(np.int32)
        for s in ready:
            self.kv.append(int(s), int(nappend[s]))
        table_full = self.kv.page_tables()
        # chunk base: the incoming token sits at the pre-append length
        start = np.where(mask, lengths0, 0).astype(np.int32)
        table = table_full.copy()
        table[~mask] = 0
        token = np.where(mask, self._last_token, 0).astype(np.int32)

        td = time.perf_counter()
        with obs_span("serving/decode_draft",
                      args={"active": int(ready.size)}, jax_annotation=True):
            if self._draft_model is None:
                drafts, self._k_pool, self._v_pool = self._draft_exe(
                    self._params, self._k_pool, self._v_pool, token, start,
                    table, nappend)
            else:
                drafts, self._draft_k, self._draft_v = self._draft_exe(
                    self._draft_params, self._draft_k, self._draft_v,
                    token, start, mask)
        drafts = np.asarray(drafts)                        # [B, K], blocks
        draft_ms = (time.perf_counter() - td) * 1000.0

        ids = np.zeros((B, K + 1), np.int32)
        ids[:, 0] = token
        ids[:, 1:] = drafts
        ids[~mask] = 0
        tv = time.perf_counter()
        with obs_span("serving/decode_verify",
                      args={"active": int(ready.size)}, jax_annotation=True):
            g, samp0, self._k_pool, self._v_pool, keys = \
                self._verify_exe(self._params, self._k_pool, self._v_pool,
                                 ids, start, nappend, table, self._keys,
                                 self._temp, self._topk)
        self._keys = np.array(keys)
        g = np.asarray(g)                                  # [B, K+1]
        samp0 = np.asarray(samp0)
        verify_ms = (time.perf_counter() - tv) * 1000.0

        out: Dict[int, List[int]] = {}
        committed_total = 0
        for s in ready:
            s = int(s)
            k_b = int(kb[s])
            a = 0
            while a < k_b and drafts[s, a] == g[s, a]:
                a += 1
            bonus = (int(samp0[s]) if self._temp[s] > 0.0 else int(g[s, a]))
            copies = self.kv.truncate(s, int(lengths0[s]) + a + 1)
            for src, dst in copies:
                self._k_pool, self._v_pool = self._copy_exe(
                    self._k_pool, self._v_pool, np.int32(src),
                    np.int32(dst))
            toks = [int(drafts[s, i]) for i in range(a)] + [bonus]
            self._last_token[s] = bonus
            out[s] = toks
            self._spec_proposed += k_b
            self._spec_accepted += a
            committed_total += len(toks)

        self._spec_steps += 1
        self._spec_slot_steps += int(ready.size)
        self._steps += 1
        self._tokens_out += committed_total
        self._spec_draft_ms = draft_ms
        self._spec_verify_ms = verify_ms
        dt_ms = (time.perf_counter() - t0) * 1000.0
        self.metrics.observe("serving/decode/step_ms", dt_ms)
        self.metrics.observe("serving/decode/step_active", int(ready.size))
        self.metrics.observe("serving/decode/draft_ms", draft_ms)
        self.metrics.observe("serving/decode/verify_ms", verify_ms)
        # amortized per-token latency: one observation per committed token
        # so the histogram's percentiles stay per-token like the plain path
        per_tok = dt_ms / max(1, committed_total)
        for _ in range(committed_total):
            self.metrics.observe("serving/decode/token_latency_ms", per_tok)
        rate = (self._spec_accepted / self._spec_proposed
                if self._spec_proposed else 0.0)
        self.metrics.gauge("decode/spec/accept_rate", rate)
        self.metrics.gauge("decode/spec/mean_accepted",
                           self._spec_accepted
                           / max(1, self._spec_slot_steps))
        self.metrics.gauge("decode/spec/draft_ms", draft_ms)
        self.metrics.gauge("decode/spec/verify_ms", verify_ms)
        return out

    def release(self, slot: int) -> None:
        """Retire a finished sequence at a token boundary: its pages return
        to the pool immediately (shared pages just drop one reference), the
        lane is reusable next step."""
        with self._lock:
            self._release_locked(int(slot))

    def _release_locked(self, slot: int) -> None:
        self.kv.free(slot)
        self._pending = [st for st in self._pending
                         if st["slot"] != slot]
        # scrub any in-flight wave entry: if the lane is re-admitted
        # before that wave exits, its stale token must not surface into
        # the new request's stream
        for w in self._wave_inflight:
            self._wave_inflight[w] = [
                s for s in self._wave_inflight[w] if s != slot]
        self._decode_ready[slot] = False
        self._last_token[slot] = 0
        self._temp[slot] = 0.0
        self._topk[slot] = 0

    def active_slots(self) -> np.ndarray:
        return self.kv.active_slots()

    # -- live weight hot-swap ------------------------------------------------

    def weights_template(self):
        """Shape/dtype template (``ShapeDtypeStruct`` tree, standard layout)
        of the ctor params — what a published tree must match leaf-for-leaf
        for :meth:`swap_params` to accept it."""
        return self._weights_template

    def swap_params(self, params, *, version: Optional[int] = None) -> bool:
        """Stage a hot swap of the serving weights. ``params`` is a flat
        list or a standard-layout pytree with every leaf's shape/dtype
        identical to the ctor tree (enforced — all compiled executables are
        reused, zero retraces). Double-buffered: the tree is packed/split/
        sharded onto devices OUTSIDE the engine lock while the old weights
        keep serving, then parked as ``_pending_swap`` and applied only at a
        fully drained token boundary (no active slots, no chunked prefills)
        so no sequence ever decodes under two versions. ``can_admit`` holds
        new admissions while a swap is pending, which drains the engine in
        bounded time under continuous load. Returns True if the swap applied
        immediately (engine idle), False if parked."""
        faults.fire("engine.swap")  # chaos hook; no-op unless armed
        if isinstance(params, (list, tuple)):
            from ..graphdef import list_to_params
            params = list_to_params(self.model, list(params))
        flat, treedef = jax.tree.flatten(params)
        want, want_def = jax.tree.flatten(self._weights_template)
        if treedef != want_def:
            raise ValueError("swapped params have a different tree "
                             "structure than the ctor params")
        for i, (got, w) in enumerate(zip(flat, want)):
            gshape = tuple(np.shape(got))
            gdtype = (np.dtype(got.dtype) if hasattr(got, "dtype")
                      else np.asarray(got).dtype)
            if gshape != tuple(w.shape) or gdtype != np.dtype(w.dtype):
                raise ValueError(
                    f"swapped params leaf {i} is {gshape}/{gdtype}, "
                    f"expected {tuple(w.shape)}/{np.dtype(w.dtype)}: hot "
                    f"swap requires unchanged shapes")
        prepared = self._prepare_params(params)  # old tree still serving
        with self._lock:
            v = (int(version) if version is not None
                 else self._serving_version + 1)
            self._pending_swap = (prepared, v)
            return self._maybe_swap_locked()

    def _maybe_swap_locked(self) -> bool:
        """Apply the pending swap iff the engine is at a fully drained token
        boundary. Caller holds ``self._lock``."""
        if self._pending_swap is None:
            return False
        if self.kv.active_slots().size or self._pending:
            return False
        params, version = self._pending_swap
        self._pending_swap = None
        self._params = params  # the swap: one reference assignment
        if self.prefix_cache:
            # old-version K/V must not seed post-swap prompts: a prefix hit
            # would splice stale activations under the new weights and break
            # bitwise parity with a cold start
            self.kv.flush_prefix_index()
        self._serving_version = version
        self._swaps += 1
        self.metrics.gauge("serving/version", float(version))
        return True

    def maybe_swap(self) -> bool:
        """Try to land a pending swap (watcher nudge for idle engines).
        Returns True if a swap applied on this call."""
        with self._lock:
            return self._maybe_swap_locked()

    def serving_version(self) -> int:
        """Version of the weights currently serving (0 = ctor weights)."""
        with self._lock:
            return self._serving_version

    # -- introspection -------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "num_slots": self.num_slots,
                "prefill_buckets": list(self.prefill_buckets),
                "max_seq_len": self.max_seq_len,
                "prefix_cache": self.prefix_cache,
                "prefill_chunk": self.prefill_chunk,
                "pending_prefills": len(self._pending),
                "aot_compiles": self.aot_compiles,
                "cold_start": (
                    None if self.exec_store is None else
                    {"dir": self.exec_store.directory,
                     "serialized_loads": self.serialized_loads,
                     "serialized_saves": self.serialized_saves}),
                "traces": self.recompile_guard.traces,
                "steady_traces": self.recompile_guard.steady_traces,
                "attention_paths": dict(self._attention_paths),
                "steps": self._steps,
                "tokens_out": self._tokens_out,
                "prefills": self._prefills,
                "serving_version": self._serving_version,
                "swaps": self._swaps,
                "pending_swap": self._pending_swap is not None,
                "kv_quant": self.kv_quant,
                "kv_quant_error": self._kv_quant_error,
                "spec": {
                    "enabled": bool(self.spec_k),
                    "k": self.spec_k,
                    "mode": ("external" if self._draft_model is not None
                             else ("self" if self.spec_k else None)),
                    "draft_layers": self.draft_layers,
                    "steps": self._spec_steps,
                    "proposed": self._spec_proposed,
                    "accepted": self._spec_accepted,
                    "accept_rate": (self._spec_accepted / self._spec_proposed
                                    if self._spec_proposed else 0.0),
                    # mean draft tokens accepted per slot per spec step
                    "mean_accepted": (self._spec_accepted
                                      / self._spec_slot_steps
                                      if self._spec_slot_steps else 0.0),
                    "draft_ms": self._spec_draft_ms,
                    "verify_ms": self._spec_verify_ms,
                },
                "kv": self.kv.stats(),
                "parallel": {
                    "mesh": (dict(self.mesh.shape)
                             if self.mesh is not None else None),
                    "tp": self._tp,
                    "ep": self._ep,
                    "pp": self._pp,
                    "stages": self._pp,
                    "pp_wave": self._pp_wave,
                    "wave_ticks": self._tick,
                    "kv_bytes_per_device": sum(
                        per_device_bytes(leaf) for leaf in
                        jax.tree.leaves((self._k_pool, self._v_pool))),
                    "param_bytes_per_device": sum(
                        per_device_bytes(leaf) for leaf in
                        jax.tree.leaves(self._params)),
                },
            }
