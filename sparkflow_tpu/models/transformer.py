"""Transformer encoder/decoder models (BERT-class) — the flagship family.

Hand-written functional JAX (no flax dependency) designed for the TPU:

- attention runs the pallas :func:`~sparkflow_tpu.ops.flash_attention` kernel
  (padding masks switch to the masked reference path), or
  :func:`~sparkflow_tpu.ops.ring_attention` over an ``sp`` mesh axis when
  sequence parallelism is enabled — long context is first-class;
- matmuls keep operands in the compute dtype (bf16 on TPU) with f32
  accumulation, layer norms and softmax statistics in f32;
- :meth:`param_pspecs` gives megatron-style tensor-parallel PartitionSpecs
  (qkv/fc1 column-sharded, o/fc2 row-sharded over ``tp``) so a ``jit`` over a
  mesh shards the model with XLA inserting the collectives;
- ``remat`` option wraps each block in ``jax.checkpoint`` to trade FLOPs for
  HBM on long sequences.

BASELINE.md's BERT-base seq-512 classification config is
``build_registry_spec('transformer_classifier', vocab_size=30522, hidden=768,
num_layers=12, num_heads=12, mlp_dim=3072, max_len=512, num_classes=N)``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import flash_attention, ring_flash_attention
from .base import RegistryModel
from .registry import register_model


def _layer_norm(x, scale, bias, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps) * scale + bias
    return y.astype(x.dtype)


def _dense(x, kernel, bias=None):
    y = jnp.matmul(x, kernel.astype(x.dtype))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


class _TransformerBase(RegistryModel):
    def __init__(self, vocab_size: int, hidden: int = 768, num_layers: int = 12,
                 num_heads: int = 12, mlp_dim: int = 3072, max_len: int = 512,
                 dropout: float = 0.1, remat: bool = False,
                 sp_axis: Optional[str] = None, compute_dtype=None):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.head_dim = hidden // num_heads
        self.mlp_dim = mlp_dim
        self.max_len = max_len
        self.dropout = dropout
        # remat: False | True/'full' (recompute everything in the block) |
        # 'dots' (save matmul outputs, recompute the cheap elementwise rest
        # — the MFU-friendly middle ground: backward skips the flops-heavy
        # recompute that full remat pays, while activation memory stays far
        # below no-remat; the standard policy for long-context training)
        if remat not in (False, True, "full", "dots"):
            raise ValueError(
                f"remat must be False, True/'full', or 'dots'; got {remat!r}")
        self.remat = remat
        self.sp_axis = sp_axis  # set to the mesh axis name for ring attention
        super().__init__(compute_dtype)

    def _remat_policy(self):
        if self.remat == "dots":
            return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return None  # full recompute

    # -- specs ---------------------------------------------------------------

    def input_specs(self):
        return {"input_ids": ((None, self.max_len), "int32"),
                "attention_mask": ((None, self.max_len), "float32")}

    def _block_specs(self):
        h, m = self.hidden, self.mlp_dim
        return {
            "ln1_scale": ((h,), "ones"), "ln1_bias": ((h,), "zeros"),
            "qkv_kernel": ((h, 3 * h), "normal(0.02)"), "qkv_bias": ((3 * h,), "zeros"),
            "o_kernel": ((h, h), "normal(0.02)"), "o_bias": ((h,), "zeros"),
            "ln2_scale": ((h,), "ones"), "ln2_bias": ((h,), "zeros"),
            "fc1_kernel": ((h, m), "normal(0.02)"), "fc1_bias": ((m,), "zeros"),
            "fc2_kernel": ((m, h), "normal(0.02)"), "fc2_bias": ((h,), "zeros"),
        }

    def param_specs(self):
        h = self.hidden
        specs = {"embed": {"tok": ((self.vocab_size, h), "normal(0.02)"),
                           "pos": ((self.max_len, h), "normal(0.02)")}}
        for i in range(self.num_layers):
            specs[f"block_{i}"] = self._block_specs()
        specs["final_ln"] = {"scale": ((h,), "ones"), "bias": ((h,), "zeros")}
        return specs

    def _block_pspecs(self):
        return {
            "ln1_scale": P(), "ln1_bias": P(),
            "qkv_kernel": P(None, "tp"), "qkv_bias": P("tp"),
            "o_kernel": P("tp", None), "o_bias": P(),
            "ln2_scale": P(), "ln2_bias": P(),
            "fc1_kernel": P(None, "tp"), "fc1_bias": P("tp"),
            "fc2_kernel": P("tp", None), "fc2_bias": P(),
        }

    def param_pspecs(self):
        """Megatron-style TP sharding rules, same tree structure as params."""
        specs = {"embed": {"tok": P(None, None), "pos": P(None, None)}}
        for i in range(self.num_layers):
            specs[f"block_{i}"] = self._block_pspecs()
        specs["final_ln"] = {"scale": P(), "bias": P()}
        return specs

    # -- forward -------------------------------------------------------------

    SUPPORTS_INT8_SERVING = True

    def _proj(self, p, base, x):
        """Dense projection through ``p[f'{base}kernel']``, consuming the
        int8-quantized form (``{base}kernel_q8``) when the serving tree was
        produced by ``quantize_for_serving`` (utils/quant.py). The result is
        cast back to ``x``'s dtype: the dynamic path rescales in f32, and
        without the cast a bf16 model's whole residual stream would silently
        promote to f32 (double activation traffic, half MXU rate)."""
        if f"{base}kernel_q8" in p:
            from ..utils.quant import quantized_dense
            return quantized_dense(x, p, self.quant_mode or "weight_only",
                                   compute_dtype=x.dtype,
                                   prefix=f"{base}kernel").astype(x.dtype)
        return _dense(x, p[f"{base}kernel"], p.get(f"{base}bias"))

    def _dropout(self, x, train, rng):
        if not train or self.dropout <= 0.0:
            return x, rng
        rng, sub = jax.random.split(rng)
        keep = 1.0 - self.dropout
        mask = jax.random.bernoulli(sub, keep, x.shape)
        return jnp.where(mask, x / keep, 0).astype(x.dtype), rng

    def _attention(self, q, k, v, mask, causal: bool):
        """[B,S,H*D] qkv already split to [B,heads,S,D]."""
        if self.sp_axis is not None:
            # pallas kernel per visiting block when shapes tile; jnp ring
            # otherwise — numerics identical either way
            return ring_flash_attention(q, k, v, self.sp_axis, causal=causal,
                                        kv_mask=mask)
        # the kernel takes the key-padding mask directly; odd shapes fall back
        # to the blockwise/reference paths inside flash_attention
        return flash_attention(q, k, v, causal=causal, kv_mask=mask)

    def _block(self, bp, x, mask, causal, train, rng, with_kv: bool = False,
               tp_axis: Optional[str] = None, ep_axis: Optional[str] = None):
        """``tp_axis``: inside a ``shard_map`` over that mesh axis, this block
        runs megatron tensor-parallel — the qkv/fc1 projections see
        column-sharded kernels (head count is derived from the *local* qkv
        width, never ``self.num_heads``), o/fc2 see row shards producing
        partial sums, and a single ``psum`` after each rejoins the replicated
        residual stream. ``ep_axis`` is consumed by the MoE mixin's overrides;
        dense blocks have no expert bank."""
        del ep_axis
        b, s, h = x.shape
        # one scope per block kind, not per layer: a profile sums the halves
        # of the stack under a few names (docs/observability.md).
        # ``attention`` groups the half's two parts: what is dense around
        # the kernel (``attn_proj``, the other families' name) and its call
        with jax.named_scope("attention"):
            with jax.named_scope("attn_proj"):
                y = _layer_norm(x, bp["ln1_scale"], bp["ln1_bias"])
                qkv = self._proj(bp, "qkv_", y)
                heads = qkv.shape[-1] // (3 * self.head_dim)
                qkv = qkv.reshape(b, s, 3, heads, self.head_dim)
                # ONE relayout for all three tensors ([B,S,3,h,d] ->
                # [3,B,h,S,d]), not three sliced transposes — TPU relayouts
                # are real copies and this is on the per-block hot path
                # (same math, layout only)
                qkv = jnp.transpose(qkv, (2, 0, 3, 1, 4))
                q, k, v = qkv[0], qkv[1], qkv[2]
            with jax.named_scope("flash_attention"):
                att = self._attention(q, k, v, mask, causal)
            with jax.named_scope("attn_proj"):
                att = jnp.transpose(att, (0, 2, 1, 3)).reshape(b, s, -1)
                att, rng = self._dropout(self._proj(bp, "o_", att), train,
                                         rng)
                if tp_axis is not None:
                    att = jax.lax.psum(att, tp_axis)
                x = x + att
        with jax.named_scope("mlp"):
            y = _layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
            y = jax.nn.gelu(self._proj(bp, "fc1_", y))
            y, rng = self._dropout(self._proj(bp, "fc2_", y), train, rng)
            if tp_axis is not None:
                y = jax.lax.psum(y, tp_axis)
            x = x + y
        if with_kv:
            # prefill path: the block's keys/values ([B,heads,S,d], local
            # heads under tp) feed the decode KV cache — same tensors
            # attention just consumed
            return x, rng, k, v
        return x, rng

    def _block_decode(self, bp, x, layer, cache, pos, attend,
                      tp_axis: Optional[str] = None,
                      ep_axis: Optional[str] = None):
        """One block applied to a single token ``x`` [B,1,hidden]; attention
        over the cached history is delegated to ``attend`` (see
        :meth:`TransformerLM.decode_step`). Same projections/norms/residuals
        as :meth:`_block` — the architecture is defined once. With
        ``tp_axis`` set (inside a shard_map) the qkv projection yields the
        shard's *local* heads, ``attend`` sees the matching heads-shard of
        the KV cache, and one ``psum`` after the O-projection / after fc2
        rejoins the replicated residual stream."""
        del ep_axis
        b, _, h = x.shape
        y = _layer_norm(x, bp["ln1_scale"], bp["ln1_bias"])
        qkv = self._proj(bp, "qkv_", y)
        heads = qkv.shape[-1] // (3 * self.head_dim)
        qkv = qkv.reshape(b, 3, heads, self.head_dim)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]          # [B, heads, d]
        att, cache = attend(layer, q, k, v, cache, pos)
        att = self._proj(bp, "o_", att.reshape(b, 1, -1))
        if tp_axis is not None:
            att = jax.lax.psum(att, tp_axis)
        x = x + att
        y = _layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
        y = jax.nn.gelu(self._proj(bp, "fc1_", y))
        y = self._proj(bp, "fc2_", y)
        if tp_axis is not None:
            y = jax.lax.psum(y, tp_axis)
        return x + y, cache

    def _block_suffix(self, bp, x, layer, cache, start, attend,
                      tp_axis: Optional[str] = None,
                      ep_axis: Optional[str] = None):
        """One block applied to a multi-token prompt *suffix* ``x``
        [B,S,hidden] whose first token sits at absolute position ``start``
        [B]; attention over (committed history ++ this chunk) is delegated to
        ``attend(layer, q, k_new, v_new, cache, start)`` with q/k/v
        ``[B, heads, S, d]``. Same projections/norms/residuals as
        :meth:`_block` — the architecture is defined once. ``tp_axis``:
        as in :meth:`_block_decode`."""
        del ep_axis
        b, s, h = x.shape
        y = _layer_norm(x, bp["ln1_scale"], bp["ln1_bias"])
        qkv = self._proj(bp, "qkv_", y)
        heads = qkv.shape[-1] // (3 * self.head_dim)
        qkv = qkv.reshape(b, s, 3, heads, self.head_dim)
        qkv = jnp.transpose(qkv, (2, 0, 3, 1, 4))
        q, k, v = qkv[0], qkv[1], qkv[2]                   # [B, heads, S, d]
        att, cache = attend(layer, q, k, v, cache, start)
        att = jnp.transpose(att, (0, 2, 1, 3)).reshape(b, s, -1)
        att = self._proj(bp, "o_", att)
        if tp_axis is not None:
            att = jax.lax.psum(att, tp_axis)
        x = x + att
        y = _layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
        y = jax.nn.gelu(self._proj(bp, "fc1_", y))
        y = self._proj(bp, "fc2_", y)
        if tp_axis is not None:
            y = jax.lax.psum(y, tp_axis)
        return x + y, cache

    def _block_aux(self, bp, x, mask, causal, train, rng):
        """Block step that also returns an auxiliary-loss contribution (zero
        for dense blocks; the MoE mixin overrides this with router aux)."""
        x, rng = self._block(bp, x, mask, causal, train, rng)
        return x, rng, jnp.zeros((), jnp.float32)

    def _encode(self, params, feeds, causal, train, rng):
        """Returns ``(encoded, mask, aux)`` — aux is the summed per-block
        auxiliary loss, threaded functionally (no mutable instance state)."""
        mask = feeds.get("attention_mask")
        with jax.named_scope("embed"):
            ids = feeds["input_ids"].astype(jnp.int32)
            b, s = ids.shape
            x = jnp.take(params["embed"]["tok"], ids, axis=0)
            if self.sp_axis is not None:
                # inside shard_map each device holds a sequence SHARD: use
                # global positions, not local 0..s-1
                offset = jax.lax.axis_index(self.sp_axis) * s
                pos = jax.lax.dynamic_slice(params["embed"]["pos"],
                                            (offset, 0), (s, self.hidden))
            else:
                pos = params["embed"]["pos"][:s]
            x = x + pos[None, :, :]
            x = self.cast(x)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        block = self._block_aux
        if self.remat:
            block = jax.checkpoint(self._block_aux, static_argnums=(3, 4),
                                   policy=self._remat_policy())
        aux_total = jnp.zeros((), jnp.float32)
        for i in range(self.num_layers):
            x, rng, aux = block(params[f"block_{i}"], x, mask, causal, train, rng)
            aux_total = aux_total + aux
        with jax.named_scope("lm_head"):    # the final norm: the head's part
            x = _layer_norm(x, params["final_ln"]["scale"],
                            params["final_ln"]["bias"])
        return x, mask, aux_total


@register_model("transformer_classifier")
class TransformerClassifier(_TransformerBase):
    """BERT-class encoder + mean-pool classification head."""

    def __init__(self, vocab_size: int, num_classes: int, **kw):
        self.num_classes = num_classes
        super().__init__(vocab_size, **kw)
        self.TENSORS = ("input_ids", "attention_mask", "y", "logits", "probs", "pred")
        from .base import _Names
        self.graphdef = _Names(self.TENSORS)

    def input_specs(self):
        specs = super().input_specs()
        specs["y"] = ((None, self.num_classes), "float32")
        return specs

    def param_specs(self):
        specs = super().param_specs()
        specs["head"] = {"kernel": ((self.hidden, self.num_classes), "normal(0.02)"),
                         "bias": ((self.num_classes,), "zeros")}
        return specs

    def param_pspecs(self):
        specs = super().param_pspecs()
        specs["head"] = {"kernel": P(None, None), "bias": P()}
        return specs

    def _forward(self, params, feeds, train, rng):
        x, mask, _ = self._encode(params, feeds, causal=False, train=train, rng=rng)
        if mask is not None:
            w = mask[:, :, None].astype(x.dtype)
            pooled = jnp.sum(x * w, axis=1) / jnp.maximum(jnp.sum(w, axis=1), 1e-6)
        else:
            pooled = jnp.mean(x, axis=1)
        logits = self._proj(params["head"], "", pooled.astype(jnp.float32))
        return {"logits": logits,
                "probs": jax.nn.softmax(logits, axis=-1),
                "pred": jnp.argmax(logits, axis=-1).astype(jnp.float32)}

    def _loss(self, params, feeds, train, rng):
        from .base import softmax_xent
        logits = self._forward(params, feeds, train, rng)["logits"]
        return softmax_xent(logits, feeds["y"])


@register_model("transformer_lm")
class TransformerLM(_TransformerBase):
    """Causal decoder LM (next-token prediction); the long-context workhorse —
    with ``sp_axis`` set its attention runs as ring attention over the mesh."""

    def __init__(self, vocab_size: int, **kw):
        super().__init__(vocab_size, **kw)
        self.TENSORS = ("input_ids", "attention_mask", "logits", "pred")
        from .base import _Names
        self.graphdef = _Names(self.TENSORS)

    def _forward(self, params, feeds, train, rng):
        x, _, _ = self._encode(params, feeds, causal=True, train=train, rng=rng)
        with jax.named_scope("lm_head"):
            logits = jnp.matmul(x.astype(jnp.float32),
                                params["embed"]["tok"].T.astype(jnp.float32))
            return {"logits": logits,
                    "pred": jnp.argmax(logits, axis=-1).astype(jnp.float32)}

    # -- autoregressive decode ----------------------------------------------
    #
    # The serving decode path (serving/decode.py) drives these; the default
    # dense cache below is the parity/test implementation, the engine swaps
    # in a paged `attend` over the shared page pool. Params are untouched —
    # param_pspecs()'s tp sharding applies to decode exactly as to training.

    def init_decode_cache(self, batch: int, max_len: Optional[int] = None,
                          dtype=None, kv_dtype: Optional[str] = None):
        """Dense per-slot KV cache ``{"k","v": [layers, B, heads, L, d]}``
        for the default :meth:`decode_step` attend. With
        ``kv_dtype="int8"|"fp8"`` the rows store quantized and the cache
        carries ``k_scale``/``v_scale`` ``[layers, B, heads, L]`` f32
        per-token-per-head scales — the dense parity twin of the serving
        engine's quantized page pool (finer scale granularity: dense writes
        are independent per position, so no running page max is needed)."""
        L = int(max_len) if max_len is not None else self.max_len
        shape = (self.num_layers, batch, self.num_heads, L, self.head_dim)
        if kv_dtype in (None, "bf16"):
            dt = dtype if dtype is not None else self.compute_dtype
            return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        from ..utils import quant
        store, _ = quant.kv_pool_dtype(kv_dtype)
        sshape = (self.num_layers, batch, self.num_heads, L)
        return {"k": jnp.zeros(shape, store), "v": jnp.zeros(shape, store),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_scale": jnp.zeros(sshape, jnp.float32)}

    def _dense_cache_attend(self, layer, q, k_new, v_new, cache, pos):
        """Default decode attention: scatter this token's k/v into a dense
        cache at ``pos`` and attend over positions ``<= pos``. q/k/v are
        ``[B, heads, d]``; ``pos`` is ``[B]`` int32. A quantized cache
        (``"k_scale" in cache``) stores each row as int8/fp8 with its own
        per-head scale; the dequant multiplies the gathered rows inside the
        f32 accumulations, mirroring the paged kernels' contract."""
        import math as _math
        from ..utils import quant
        b = q.shape[0]
        L = cache["k"].shape[3]
        bidx = jnp.arange(b)
        quantized = "k_scale" in cache
        if quantized:
            qmax = (127.0 if cache["k"].dtype == jnp.int8 else 448.0)

            def put(rows, scales, new):
                nf = new.astype(jnp.float32)                  # [B, heads, d]
                sc = jnp.max(jnp.abs(nf), axis=-1) / qmax     # [B, heads]
                eff = jnp.where(sc > 0, sc, 1.0)
                rq = quant.kv_cast(nf / eff[..., None], rows.dtype, qmax)
                rows = rows[layer].at[bidx, :, pos].set(rq)
                scales = scales[layer].at[bidx, :, pos].set(sc)
                return rows, scales

            k, ks = put(cache["k"], cache["k_scale"], k_new)
            v, vs = put(cache["v"], cache["v_scale"], v_new)
            kf = k.astype(jnp.float32) * ks[..., None]
            vf = v.astype(jnp.float32) * vs[..., None]
        else:
            k = cache["k"][layer].at[bidx, :, pos].set(
                k_new.astype(cache["k"].dtype))
            v = cache["v"][layer].at[bidx, :, pos].set(
                v_new.astype(cache["v"].dtype))
            kf = k.astype(jnp.float32)
            vf = v.astype(jnp.float32)
        scale = 1.0 / _math.sqrt(self.head_dim)
        s = jnp.einsum("bhd,bhld->bhl", q.astype(jnp.float32), kf) * scale
        valid = jnp.arange(L, dtype=jnp.int32)[None, :] <= pos[:, None]
        s = jnp.where(valid[:, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhl,bhld->bhd", p, vf)
        cache = dict(cache, k=cache["k"].at[layer].set(k),
                     v=cache["v"].at[layer].set(v))
        if quantized:
            cache["k_scale"] = cache["k_scale"].at[layer].set(ks)
            cache["v_scale"] = cache["v_scale"].at[layer].set(vs)
        return out.astype(q.dtype), cache

    # -- stage-level pieces ---------------------------------------------------
    #
    # The pipeline-parallel decode engine (serving/decode.py with
    # ``pp_axis`` set) rebuilds decode_step/prefill/... as STAGED programs:
    # every pp stage holds only its own blocks (parallel/pp.py layout), so
    # the embed / per-block / head pieces must be callable separately, with
    # stage-LOCAL layer indices. Each whole-model method below is the
    # composition of these pieces — the architecture stays defined once.

    def decode_embed(self, params, token, pos):
        """Embed one token per row: ``token``/``pos`` [B] int32 ->
        [B, 1, hidden] in compute dtype. ``params`` needs only the shared
        (stage-replicated) ``embed`` subtree."""
        token = token.astype(jnp.int32)
        pos = pos.astype(jnp.int32)
        x = jnp.take(params["embed"]["tok"], token, axis=0)
        posemb = jnp.take(params["embed"]["pos"],
                          jnp.clip(pos, 0, self.max_len - 1), axis=0)
        return self.cast(x + posemb)[:, None, :]

    def suffix_embed(self, params, ids, start):
        """Embed a token block ``ids`` [B,S] whose first token sits at
        absolute position ``start`` [B] -> [B, S, hidden]."""
        ids = ids.astype(jnp.int32)
        s = ids.shape[1]
        start = start.astype(jnp.int32)
        x = jnp.take(params["embed"]["tok"], ids, axis=0)
        pos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        posemb = jnp.take(params["embed"]["pos"],
                          jnp.clip(pos, 0, self.max_len - 1), axis=0)
        return self.cast(x + posemb)

    def prefill_embed(self, params, ids):
        """Embed a full (padded) prompt ``ids`` [B,S] -> [B, S, hidden]."""
        ids = ids.astype(jnp.int32)
        s = ids.shape[1]
        x = jnp.take(params["embed"]["tok"], ids, axis=0)
        return self.cast(x + params["embed"]["pos"][:s][None, :, :])

    def head_all(self, params, x):
        """Final LN + tied-embedding head at every position:
        x [B,S,hidden] -> logits [B,S,vocab] f32."""
        x = _layer_norm(x, params["final_ln"]["scale"],
                        params["final_ln"]["bias"])
        return jnp.matmul(x.astype(jnp.float32),
                          params["embed"]["tok"].T.astype(jnp.float32))

    def decode_head(self, params, x):
        """Final LN + tied head for a single-token activation
        x [B,1,hidden] -> logits [B,vocab] f32."""
        return self.head_all(params, x)[:, 0]

    def head_last(self, params, x, lengths=None):
        """Final LN + tied head at the last valid position of x [B,S,hidden]
        (``lengths`` [B] counts valid tokens, default S) -> [B,vocab] f32."""
        b, s, _ = x.shape
        x = _layer_norm(x, params["final_ln"]["scale"],
                        params["final_ln"]["bias"])
        if lengths is None:
            last = jnp.full((b,), s - 1, jnp.int32)
        else:
            last = jnp.clip(lengths.astype(jnp.int32) - 1, 0, s - 1)
        x_last = x[jnp.arange(b), last]                    # [B, hidden]
        return jnp.matmul(x_last.astype(jnp.float32),
                          params["embed"]["tok"].T.astype(jnp.float32))

    def block_decode(self, bp, x, layer, cache, pos, attend,
                     tp_axis: Optional[str] = None,
                     ep_axis: Optional[str] = None):
        """Public single-block decode step (see :meth:`_block_decode`);
        ``layer`` is whatever index ``attend`` expects — the pp engine passes
        stage-local indices against a layers-sharded pool."""
        return self._block_decode(bp, x, layer, cache, pos, attend,
                                  tp_axis=tp_axis, ep_axis=ep_axis)

    def block_suffix(self, bp, x, layer, cache, start, attend,
                     tp_axis: Optional[str] = None,
                     ep_axis: Optional[str] = None):
        """Public single-block suffix step (see :meth:`_block_suffix`)."""
        return self._block_suffix(bp, x, layer, cache, start, attend,
                                  tp_axis=tp_axis, ep_axis=ep_axis)

    def block_prefill(self, bp, x, mask=None,
                      tp_axis: Optional[str] = None,
                      ep_axis: Optional[str] = None):
        """Public single-block causal prefill step returning this block's
        keys/values for the decode cache: ``(x, k, v)`` with k/v
        [B,heads,S,d] (local heads under tp)."""
        x, _, k, v = self._block(bp, x, mask, True, False,
                                 jax.random.PRNGKey(0), with_kv=True,
                                 tp_axis=tp_axis, ep_axis=ep_axis)
        return x, k, v

    def decode_step(self, params, cache, token, pos, attend=None,
                    num_layers: Optional[int] = None,
                    tp_axis: Optional[str] = None,
                    ep_axis: Optional[str] = None):
        """Single-token autoregressive apply: embed ``token`` [B] int32 at
        position ``pos`` [B] int32, run every block over the cached history,
        return ``(logits [B, vocab] f32, cache)``.

        ``attend(layer, q, k_new, v_new, cache, pos) -> (att [B,heads,d],
        cache)`` owns the KV cache layout; the default uses the dense cache
        from :meth:`init_decode_cache`, the serving engine passes a paged
        closure over :func:`~sparkflow_tpu.ops.paged_attention`.

        ``num_layers`` truncates the stack to its first N blocks (then the
        usual final LN + tied-embedding head) — the self-speculation draft:
        the truncated model's layer-i K/V is *identical* to the full model's,
        so a draft pass can read and write the same paged pool the verify
        pass uses, no separate draft cache or prefill needed.

        ``tp_axis``/``ep_axis``: mesh axes for tensor-/expert-parallel decode
        inside a ``shard_map`` — params and cache arrive as per-shard slices,
        activations stay replicated (see :meth:`_block_decode`). Note the
        row-parallel biases (``o_bias``/``fc2_bias``) must be pre-divided by
        the tp degree by the caller so the psum restores them exactly once
        (serving/decode.py does this when placing params)."""
        if attend is None:
            attend = self._dense_cache_attend
        L = self.num_layers if num_layers is None else int(num_layers)
        pos = pos.astype(jnp.int32)
        x = self.decode_embed(params, token, pos)          # [B, 1, hidden]
        for i in range(L):
            x, cache = self._block_decode(params[f"block_{i}"], x, i, cache,
                                          pos, attend, tp_axis=tp_axis,
                                          ep_axis=ep_axis)
        return self.decode_head(params, x), cache

    def decode_verify(self, params, ids, start, cache, attend,
                      tp_axis: Optional[str] = None,
                      ep_axis: Optional[str] = None):
        """Speculative-verify forward: like :meth:`prefill_suffix` (``ids``
        [B,S] starting at absolute position ``start`` [B], attention over
        committed history + this chunk delegated to ``attend``) but projects
        logits at **every** position — ``(logits [B, S, vocab] f32, cache)``
        — so one call scores a drafted token block: ``logits[:, j]`` is the
        target model's next-token distribution after prefix + drafts[:j].
        ``tp_axis``/``ep_axis``: as in :meth:`decode_step`."""
        start = start.astype(jnp.int32)
        x = self.suffix_embed(params, ids, start)
        for i in range(self.num_layers):
            x, cache = self._block_suffix(params[f"block_{i}"], x, i, cache,
                                          start, attend, tp_axis=tp_axis,
                                          ep_axis=ep_axis)
        return self.head_all(params, x), cache

    def prefill(self, params, ids, mask=None, lengths=None,
                tp_axis: Optional[str] = None,
                ep_axis: Optional[str] = None):
        """Causal forward over a (padded) prompt that also returns each
        block's keys/values for the decode cache: ``(logits [B, vocab] at
        the last valid position, [(k, v)] * layers with k/v [B,heads,S,d])``.
        ``lengths`` [B] selects the position whose logits seed generation
        (default: the full row, ``S``). ``tp_axis``/``ep_axis``: as in
        :meth:`decode_step`; under tp the returned k/v carry the shard's
        *local* heads — exactly the slice its heads-sharded pool stores."""
        x = self.prefill_embed(params, ids)
        kvs = []
        for i in range(self.num_layers):
            x, k, v = self.block_prefill(params[f"block_{i}"], x, mask,
                                         tp_axis=tp_axis, ep_axis=ep_axis)
            kvs.append((k, v))
        return self.head_last(params, x, lengths), kvs

    def prefill_suffix(self, params, ids, start, cache, attend, lengths=None,
                       tp_axis: Optional[str] = None,
                       ep_axis: Optional[str] = None):
        """Prefill a prompt **suffix**: like :meth:`prefill` but the first
        token of ``ids`` [B,S] sits at absolute position ``start`` [B] int32
        (position embeddings offset accordingly) and attention over the
        already-committed prefix K/V is delegated to
        ``attend(layer, q, k, v, cache, start) -> (att [B,heads,S,d], cache)``
        — the cache owner defines the layout (the serving engine writes the
        chunk's K/V into pool pages and attends over the whole page table).
        This is what makes shared-prefix caching and chunked prefill work:
        only the un-shared / not-yet-committed tokens are ever forwarded.
        Returns ``(logits [B, vocab] at the last valid suffix position,
        cache)``; ``lengths`` [B] counts valid suffix tokens (default S)."""
        start = start.astype(jnp.int32)
        x = self.suffix_embed(params, ids, start)
        for i in range(self.num_layers):
            x, cache = self._block_suffix(params[f"block_{i}"], x, i, cache,
                                          start, attend, tp_axis=tp_axis,
                                          ep_axis=ep_axis)
        return self.head_last(params, x, lengths), cache

    def _loss(self, params, feeds, train, rng):
        logits = self._forward(params, feeds, train, rng)["logits"]
        with jax.named_scope("lm_head"):    # the cross-entropy of the logits
            logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            tgt = feeds["input_ids"].astype(jnp.int32)[:, 1:]
            nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
            if ("attention_mask" in feeds
                    and feeds["attention_mask"] is not None):
                w = feeds["attention_mask"][:, 1:].astype(jnp.float32)
                return (jnp.sum(nll * w, axis=-1)
                        / jnp.maximum(jnp.sum(w, axis=-1), 1e-6))
            return jnp.mean(nll, axis=-1)
