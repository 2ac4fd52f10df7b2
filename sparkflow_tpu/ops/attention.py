"""Attention kernels: pallas flash attention + ring attention (sequence parallel).

Nothing like this exists in the reference (it has no attention or sequence code
at all — SURVEY.md §5 "Long-context"); these ops are the long-context foundation
of the framework's transformer models.

Layout convention: ``[batch, heads, seq, head_dim]``.

- :func:`flash_attention`: single-device fused attention. The pallas kernel
  tiles Q into ``block_q`` rows and streams K/V in ``block_k`` columns with the
  online-softmax recurrence, so the S x S score matrix never hits HBM; scores
  accumulate in f32 on the MXU regardless of input dtype. Falls back to a pure
  jnp implementation off-TPU (CPU tests) and for tiny shapes where tiling
  constraints don't hold.

- :func:`ring_attention`: attention over a sequence-sharded mesh axis (``sp``).
  Each device holds S/n of Q/K/V; K/V shards rotate around the ring via
  ``ppermute`` (ICI neighbor exchange) for n steps while each device folds the
  visiting block into its running (max, sum, acc) softmax state. Communication
  overlaps compute and per-device memory stays O(S/n) — the standard TPU
  long-context recipe (Liu et al., Ring Attention; jax-ml scaling-book §sharding).
"""

from __future__ import annotations

import contextlib
import functools
import logging
import math
from contextvars import ContextVar
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger(__name__)

NEG_INF = -1e30

# Trace-time switch: pallas_call lowers to a custom call that GSPMD has no
# partitioning rule for, so under a sharded jit the kernel's operands may be
# sharded and the compiled program would replicate them (all-gather) or fail
# outright. Sharded train-step builders trace under sharded_attention()
# (below), which keeps the kernel by nesting a shard_map; this explicit
# override forces the GSPMD-partitionable blockwise path unconditionally —
# for tests and for callers that need the partitioner to own attention.
_FORCE_XLA: ContextVar[bool] = ContextVar("sparkflow_force_xla_attention",
                                          default=False)


@contextlib.contextmanager
def force_xla_attention():
    """Within this context (including jit *tracing* started inside it),
    :func:`flash_attention` routes to the XLA blockwise/reference path instead
    of the pallas kernel. See the note on ``_FORCE_XLA`` above."""
    tok = _FORCE_XLA.set(True)
    try:
        yield
    finally:
        _FORCE_XLA.reset(tok)


# Sharded-jit attention: GSPMD cannot partition the pallas custom call, but
# attention is embarrassingly parallel over batch and heads — so instead of
# forfeiting the kernel on every >1-device mesh (the old blanket
# force_xla_attention), sharded traces set this context and flash_attention
# wraps ITSELF in a nested shard_map over (batch x heads), running the
# pallas kernel per shard with zero communication. Falls back to the
# blockwise path when the dims don't divide the mesh axes.
_SHARD_ATTN: ContextVar = ContextVar("sparkflow_shard_attention",
                                     default=None)


@contextlib.contextmanager
def sharded_attention(mesh, batch_axis: str = "dp", head_axis: str = "tp"):
    """Within this context (including jit tracing started inside it),
    :func:`flash_attention` runs the pallas kernel per (batch, heads) shard
    via shard_map over ``mesh`` instead of degrading to XLA blockwise."""
    tok = _SHARD_ATTN.set((mesh, batch_axis, head_axis))
    try:
        yield
    finally:
        _SHARD_ATTN.reset(tok)


@contextlib.contextmanager
def unsharded_attention():
    """Within this context (including jit tracing started inside it),
    :func:`flash_attention` ignores any enclosing :func:`sharded_attention`
    — for step builders that manage their OWN shard_map (pp/sp): their
    bodies run per-shard already, and re-wrapping the kernel in a nested
    shard_map over the same mesh axes would be invalid."""
    tok = _SHARD_ATTN.set(None)
    try:
        yield
    finally:
        _SHARD_ATTN.reset(tok)


def _try_shardmap_flash(q, k, v, kv_mask, causal, scale, interpret,
                        block_q=None, block_k=None):
    """shard_map-wrapped flash for sharded-jit traces, or None when the
    context is unset / the shapes don't divide the mesh axes."""
    ctx = _SHARD_ATTN.get()
    if ctx is None:
        return None
    mesh, ba, ha = ctx
    bsz = int(mesh.shape.get(ba, 1))
    hsz = int(mesh.shape.get(ha, 1))
    b, h = q.shape[0], q.shape[1]
    if bsz * hsz <= 1 or b % bsz or h % hsz:
        return None
    from jax.sharding import PartitionSpec as P

    bspec = ba if bsz > 1 else None
    hspec = ha if hsz > 1 else None
    qkv_spec = P(bspec, hspec)

    def inner(q, k, v, *m):
        # the body must not recurse into the wrapper, and per-shard
        # divisibility/tiling decisions are flash_attention's own;
        # explicitly pinned tile sizes stay pinned per shard (the
        # documented contract)
        tok = _SHARD_ATTN.set(None)
        try:
            return flash_attention(q, k, v, causal=causal, sm_scale=scale,
                                   interpret=interpret,
                                   block_q=block_q, block_k=block_k,
                                   kv_mask=m[0] if m else None)
        finally:
            _SHARD_ATTN.reset(tok)

    in_specs = (qkv_spec, qkv_spec, qkv_spec)
    args = (q, k, v)
    if kv_mask is not None:
        in_specs += (P(bspec),)
        args += (kv_mask,)
    return jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                         out_specs=qkv_spec, check_vma=False)(*args)


# Which path the most recent flash_attention TRACE took ('pallas',
# 'blockwise', or 'reference'). chip_smoke.py asserts it is 'pallas' after
# its train step compiled on the chip, tests/test_tpu_compile.py after
# compiling for a described one: a kernel edit that breaks the tile rules
# would otherwise fall back silently (the round-2 (8,128)-tile regression).
# A ContextVar (like _FORCE_XLA/_SHARD_ATTN) so an interleaved trace in
# another thread cannot clobber the value between a caller's compile and
# its last_attention_path() check.
_LAST_PATH: ContextVar = ContextVar("sparkflow_last_attention_path",
                                    default=None)


def last_attention_path():
    """Path taken by the most recent :func:`flash_attention` call (at trace
    time for jitted callers) in this thread/context: 'pallas' | 'blockwise'
    | 'reference' | None."""
    return _LAST_PATH.get()


# Every kernel entry point's decision inside the active
# record_attention_paths() block, as "<entry point>:<path>" strings — one
# program often traces several attention calls, and last_attention_path()
# keeps only the final one.
_PATH_LOG: ContextVar = ContextVar("sparkflow_attention_path_log",
                                   default=None)


@contextlib.contextmanager
def record_attention_paths():
    """Yields a list that collects ``"<entry point>:<path>"`` for every
    attention entry point traced inside the block (e.g.
    ``"paged_attention:pallas"``, ``"flash_attention:reference"``)."""
    log: list = []
    tok = _PATH_LOG.set(log)
    try:
        yield log
    finally:
        _PATH_LOG.reset(tok)


def _log_path(kernel: str, path: str) -> None:
    log = _PATH_LOG.get()
    if log is not None:
        log.append(f"{kernel}:{path}")


def _note_path(kernel: str, path: str) -> None:
    _LAST_PATH.set(path)
    _log_path(kernel, path)


_WARNED: set = set()


def _warn_reference(kernel: str, shape, dtype, rule: str) -> None:
    """On a TPU backend, a kernel entry point that leaves its pallas kernel
    says so: once per (kernel, shape, rule), at warning level. Off the TPU
    the jnp paths are the expected ones and stay quiet."""
    key = (kernel, tuple(shape), str(dtype), rule)
    if jax.default_backend() != "tpu" or key in _WARNED:
        return
    _WARNED.add(key)
    logger.warning("%s: shape %s %s takes the XLA reference path, not the "
                   "pallas kernel: %s", kernel, tuple(shape), dtype, rule)


# ---------------------------------------------------------------------------
# Reference (jnp) implementation — ground truth for tests + CPU fallback
# ---------------------------------------------------------------------------


def attention_reference(q, k, v, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        q_offset: int = 0, k_offset: int = 0, kv_mask=None):
    """Plain softmax attention, f32 accumulation. Shapes [B,H,S,D];
    ``kv_mask`` [B,S_k] masks padded keys (1 = attend)."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0) + q_offset
        ki = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1) + k_offset
        s = jnp.where(qi >= ki, s, NEG_INF)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] > 0, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Pallas flash attention (TPU)
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, *rest, sm_scale: float, causal: bool,
                  block_q: int, block_k: int, has_mask: bool):
    if has_mask:
        mask_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
        mask_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0]                               # [block_q, d] input dtype
        k = k_ref[0]                               # [block_k, d]
        v = v_ref[0]                               # [block_k, d]
        # native-dtype operands on the MXU, f32 accumulation
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + qi * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * block_k
            s = jnp.where(rows >= cols, s, NEG_INF)
        if mask_ref is not None:  # [1, block_k] key-padding mask for this batch row
            s = jnp.where(mask_ref[0] > 0, s, NEG_INF)

        m_prev = m_ref[:]                          # [block_q, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # [block_q, block_k] f32
        alpha = jnp.exp(m_prev - m_new)            # [block_q, 1]
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = alpha * acc_ref[:] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    if causal:
        # blocks entirely above the diagonal contribute nothing — skip them
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)
        # logsumexp per q row — the backward kernels recompute p from it.
        # Kept [block_q, 1]: a trailing unit dim makes the block legal under
        # the TPU (8, 128) tile rule (a [1, block_q] block is not)
        lse_ref[0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _flash_pallas_forward(q, k, v, kv_mask, causal, scale, block_q, block_k,
                          interpret, with_lse=False):
    b, h, s, d = q.shape
    sk = k.shape[2]
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    has_mask = kv_mask is not None

    kernel = functools.partial(_flash_kernel, sm_scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               has_mask=has_mask)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
    ]
    args = [qf, kf, vf]
    if has_mask:
        # per-batch key mask as [B, 1, Sk]; block row selected by bh // h
        # (the unit middle dim keeps the [1, 1, block_k] block tile-legal)
        in_specs.append(pl.BlockSpec((1, 1, block_k),
                                     lambda bh, qi, ki, _h=h: (bh // _h, 0, ki)))
        args.append(kv_mask.astype(jnp.float32)[:, None, :])
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(b * h, s // block_q, sk // block_k),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
                   pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0))),
        out_shape=(jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    out = out.reshape(b, h, s, d)
    if with_lse:
        return out, lse.reshape(b, h, s)
    return out


# Tile-legal [1, block, 1] block over a [bh, s, 1] row-statistics array —
# shared by the lse/delta operands of the forward and backward kernels
def _row_stat_spec(block, order="qk"):
    if order == "qk":   # grid (bh, qi, ki)
        return pl.BlockSpec((1, block, 1), lambda bh_, qi, ki: (bh_, qi, 0))
    return pl.BlockSpec((1, block, 1), lambda bh_, ki, qi: (bh_, qi, 0))


def _blockwise_attention(q, k, v, kv_mask, causal, scale, block_k=512):
    """Differentiable blockwise attention in pure jnp: lax.scan over K/V
    blocks with the online-softmax fold, each block rematerialized — O(S*block)
    live memory instead of O(S^2). This is the autodiff path behind the pallas
    kernel's custom_vjp (gradients recompute flash-style; the S x S score
    matrix never materializes in either direction)."""
    b, h, s, d = q.shape
    sk = k.shape[2]
    block_k = min(block_k, sk)
    if sk % block_k:
        # can't tile: the dense reference path, mask honored
        return attention_reference(q, k, v, causal, scale, kv_mask=kv_mask)
    nblk = sk // block_k
    kb = k.reshape(b, h, nblk, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nblk, block_k, d).transpose(2, 0, 1, 3, 4)
    if kv_mask is not None:
        mb = kv_mask.reshape(b, nblk, block_k).transpose(1, 0, 2)
    else:
        mb = jnp.ones((nblk, b, 1), jnp.float32)  # dummy, unused

    @jax.checkpoint
    def fold(carry, blk):
        acc, m, l = carry
        kc, vc, mc, idx = blk
        a2, m2, l2 = _block_stats(q, kc, vc, scale, causal, 0, idx * block_k,
                                  mc if kv_mask is not None else None)
        return _merge_stats(acc, m, l, a2, m2, l2), None

    init = (jnp.zeros((b, h, s, d), jnp.float32),
            jnp.full((b, h, s, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, h, s, 1), jnp.float32))
    (acc, m, l), _ = jax.lax.scan(fold, init, (kb, vb, mb, jnp.arange(nblk)))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash attention backward (flash-style recompute)
#
# Standard recurrence (Dao, FlashAttention-2): with row stats L = logsumexp
# saved by the forward and D_i = rowsum(dO_i * O_i),
#   P   = exp(S - L);  dV = P^T dO;  dP = dO V^T
#   dS  = P * (dP - D);  dQ = scale * dS K;  dK = scale * dS^T Q
# The S x S matrices exist only block-by-block in VMEM, same as the forward.
#
# Two schedules of the same tile arithmetic (_bwd_tile), chosen by shape in
# _flash_pallas_backward_flat:
# - fused, ``flash_bwd_dqkv``: one kernel on the grid (bh, ki, qi). A visited
#   tile's P and dS are made once and feed all three sums: dK/dV accumulate
#   over qi in [block_k, d] scratch, dQ over ki in a float32 scratch that
#   holds the head's whole [s, d] (the TPU grid is sequential, so it stays in
#   VMEM from the head's first key tile to its last).
# - split, ``flash_bwd_dq`` (grid (bh, qi, ki)) and ``flash_bwd_dkv`` (grid
#   (bh, ki, qi)): each makes P and dS for itself, 7 products a tile where
#   the fused kernel has 5; no buffer grows with s, so this is the schedule
#   of sequences whose dQ does not fit _FUSED_DQ_VMEM_BUDGET.
# Both add a tile's terms in the same order (dQ over key tiles ascending,
# dK/dV over query tiles ascending): at equal tiles their results are
# bit-equal.
# ---------------------------------------------------------------------------


def _bwd_p_block(q, k, lse, sm_scale, causal, qi0, ki0, mask_blk):
    """Recompute the normalized probability block P = exp(S - L) [bq, bk];
    masked/causal-excluded entries are exactly 0 (no exp of NEG_INF deltas).
    ``lse`` is [bq, 1]; ``mask_blk`` is [1, bk] (both broadcast over S)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + qi0
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki0
        s = jnp.where(rows >= cols, s, NEG_INF)
    if mask_blk is not None:
        s = jnp.where(mask_blk > 0, s, NEG_INF)
    # rows with every key masked have lse ~ NEG_INF; gate on s to keep p = 0
    return jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - lse), 0.0)


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
              qi, ki, sm_scale, causal, block_q, block_k):
    """One visited tile's P and dS, [block_q, block_k] float32, and dO as
    the float32 operand the sums take it as."""
    do = do_ref[0].astype(jnp.float32)
    p = _bwd_p_block(q_ref[0], k_ref[0], lse_ref[0], sm_scale, causal,
                     qi * block_q, ki * block_k,
                     mask_ref[0] if mask_ref is not None else None)
    dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta_ref[0]), do


def _when_visited(causal, qi, ki, block_q, block_k, compute):
    """Run ``compute`` unless the tile lies entirely above the causal
    diagonal, where P = 0 and every sum gets nothing."""
    if causal:
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(compute)
    else:
        compute()


def _dq_term(ds, k_ref, sm_scale):
    """dQ_i's term of one tile: scale * dS K."""
    return sm_scale * jax.lax.dot_general(
        ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dkv_terms(p, ds, do, q_ref, sm_scale):
    """dK_j's and dV_j's terms of one tile: scale * dS^T Q and P^T dO."""
    dv = jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dk = sm_scale * jax.lax.dot_general(
        ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return dk, dv


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         *rest, sm_scale, causal, block_q, block_k, has_mask):
    if has_mask:
        mask_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
        mask_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        _, ds, _ = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             mask_ref, qi, ki, sm_scale, causal, block_q,
                             block_k)
        dq_acc[:] += _dq_term(ds, k_ref, sm_scale)

    _when_visited(causal, qi, ki, block_q, block_k, _compute)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          *rest, sm_scale, causal, block_q, block_k, has_mask):
    if has_mask:
        mask_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
        mask_ref = None
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        p, ds, do = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                              mask_ref, qi, ki, sm_scale, causal, block_q,
                              block_k)
        dk, dv = _dkv_terms(p, ds, do, q_ref, sm_scale)
        dk_acc[:] += dk
        dv_acc[:] += dv

    # q blocks entirely above this k block's diagonal see p = 0
    _when_visited(causal, qi, ki, block_q, block_k, _compute)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dqkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           *rest, sm_scale, causal, block_q, block_k,
                           has_mask):
    if has_mask:
        mask_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = rest
    else:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = rest
        mask_ref = None
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nk = pl.num_programs(1)
    nq = pl.num_programs(2)
    # tile qi's rows of the head's dQ: dq_acc and dq_ref hold all s of them
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    @pl.when(ki == 0)
    def _init_dq():
        dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]), jnp.float32)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        p, ds, do = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                              mask_ref, qi, ki, sm_scale, causal, block_q,
                              block_k)
        dk, dv = _dkv_terms(p, ds, do, q_ref, sm_scale)
        dk_acc[:] += dk
        dv_acc[:] += dv
        dq_acc[rows, :] += _dq_term(ds, k_ref, sm_scale)

    _when_visited(causal, qi, ki, block_q, block_k, _compute)

    @pl.when(qi == nq - 1)
    def _finalize_dkv():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    # the dQ block's index follows bh alone: it leaves VMEM after the head's
    # last grid step, by when the last key tile has written every row
    @pl.when(ki == nk - 1)
    def _finalize_dq():
        dq_ref[0, rows, :] = dq_acc[rows, :].astype(dq_ref.dtype)


def _flash_bwd_prep(q, out, lse, g):
    """Flatten the q-side operands and compute D = rowsum(dO * O) — all
    independent of the k/v side, so ring backward hoists this out of the
    per-visit loop. Row statistics travel as [bh, s, 1]: tile-legal
    [1, block_q, 1] blocks (the layout the forward emits lse in)."""
    b, h, s, d = q.shape
    bh = b * h
    qf = q.reshape(bh, s, d)
    gf = g.reshape(bh, s, d)
    lsef = lse.reshape(bh, s, 1)
    # D_i = rowsum(dO * O): tiny elementwise reduce, XLA fuses it fine
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, s, 1)
    return qf, gf, lsef, delta


def _flash_pallas_backward(q, k, v, kv_mask, out, lse, g, causal, scale,
                           block_q, block_k, interpret):
    qf, gf, lsef, delta = _flash_bwd_prep(q, out, lse, g)
    b, h, _, d = q.shape
    kf = k.reshape(b * h, -1, d)
    vf = v.reshape(b * h, -1, d)
    maskf = (kv_mask.astype(jnp.float32)[:, None, :]
             if kv_mask is not None else None)
    dq, dk, dv = _flash_pallas_backward_flat(
        qf, kf, vf, gf, lsef, delta, maskf, h, causal, scale,
        block_q, block_k, interpret)
    s, sk = q.shape[2], k.shape[2]
    return (dq.reshape(b, h, s, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


# What the fused backward may keep in VMEM for one head's dQ: the float32
# accumulator and the two buffers of its output block, each [s, d] with d
# padded to the 128 lanes. 16 MiB holds s = 16 384 in bfloat16 and 8 192 in
# float32 at any d <= 128 (on a v5e the fused kernel is 0.67-0.70 x the pair
# up to there: PERF.md section 6, PR 37); the kernel's tiles and operands
# need 10 MB more (_FUSED_VMEM_LIMIT, of the chip's 128 MiB).
_FUSED_DQ_VMEM_BUDGET = 16 * 1024 * 1024
_FUSED_VMEM_LIMIT = 48 * 1024 * 1024


def _bwd_is_fused(s: int, d: int, dtype) -> bool:
    lanes = -(-d // 128) * 128
    dq_bytes = s * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)
    return dq_bytes <= _FUSED_DQ_VMEM_BUDGET


def _flash_pallas_backward_flat(qf, kf, vf, gf, lsef, delta, maskf, h,
                                causal, scale, block_q, block_k, interpret):
    """dQ, dK, dV of flat ``[bh, s, d]`` operands: the fused kernel where a
    head's dQ fits its VMEM budget, the dq and dkv kernels otherwise."""
    _, s, d = qf.shape
    fused = _bwd_is_fused(s, d, qf.dtype)
    _log_path("flash_attention_bwd", "fused" if fused else "split")
    backward = _flash_bwd_fused_flat if fused else _flash_bwd_split_flat
    return backward(qf, kf, vf, gf, lsef, delta, maskf, h, causal, scale,
                    block_q, block_k, interpret)


def _bwd_kq_operands(qf, kf, vf, gf, lsef, delta, maskf, h, block_q, block_k):
    """Input block specs and operands on the grid (bh, ki, qi), and the key
    tile's spec, which is also dK's and dV's output spec."""
    d = qf.shape[2]
    qspec = pl.BlockSpec((1, block_q, d), lambda bh_, ki, qi: (bh_, qi, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda bh_, ki, qi: (bh_, ki, 0))
    row_q = _row_stat_spec(block_q, "kq")
    in_specs = [qspec, kspec, kspec, qspec, row_q, row_q]
    args = [qf, kf, vf, gf, lsef, delta]
    if maskf is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda bh_, ki, qi, _h=h: (bh_ // _h, 0, ki)))
        args.append(maskf)
    return in_specs, args, kspec


def _flash_bwd_fused_flat(qf, kf, vf, gf, lsef, delta, maskf, h, causal,
                          scale, block_q, block_k, interpret):
    bh, s, d = qf.shape
    sk = kf.shape[1]
    in_specs, args, kspec = _bwd_kq_operands(qf, kf, vf, gf, lsef, delta,
                                             maskf, h, block_q, block_k)
    return pl.pallas_call(
        functools.partial(_flash_bwd_dqkv_kernel, sm_scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          has_mask=maskf is not None),
        name="flash_bwd_dqkv",
        grid=(bh, sk // block_k, s // block_q),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((1, s, d), lambda bh_, ki, qi: (bh_, 0, 0)),
                   kspec, kspec),
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), qf.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), kf.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), vf.dtype)),
        scratch_shapes=[pltpu.VMEM((s, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        # dQ sums over ki and dK/dV over qi: only the heads are independent
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_FUSED_VMEM_LIMIT),
        interpret=interpret,
    )(*args)


def _flash_bwd_split_flat(qf, kf, vf, gf, lsef, delta, maskf, h, causal,
                          scale, block_q, block_k, interpret):
    bh, s, d = qf.shape
    sk = kf.shape[1]
    has_mask = maskf is not None

    common = dict(sm_scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, has_mask=has_mask)
    qspec = pl.BlockSpec((1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0))
    row_q = _row_stat_spec(block_q, "qk")

    in_specs_dq = [
        qspec,
        pl.BlockSpec((1, block_k, d), lambda bh_, qi, ki: (bh_, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh_, qi, ki: (bh_, ki, 0)),
        qspec, row_q, row_q,
    ]
    args_dq = [qf, kf, vf, gf, lsef, delta]
    if has_mask:
        in_specs_dq.append(pl.BlockSpec(
            (1, 1, block_k), lambda bh_, qi, ki, _h=h: (bh_ // _h, 0, ki)))
        args_dq.append(maskf)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        name="flash_bwd_dq",
        grid=(bh, s // block_q, sk // block_k),
        in_specs=in_specs_dq,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), qf.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args_dq)

    in_specs_kv, args_kv, kspec = _bwd_kq_operands(
        qf, kf, vf, gf, lsef, delta, maskf, h, block_q, block_k)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        name="flash_bwd_dkv",
        grid=(bh, sk // block_k, s // block_q),
        in_specs=in_specs_kv,
        out_specs=(kspec, kspec),
        out_shape=(jax.ShapeDtypeStruct((bh, sk, d), kf.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), vf.dtype)),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args_kv)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, kv_mask, causal, scale, block_q, block_k,
           bwd_block_q, bwd_block_k, interpret):
    return _flash_pallas_forward(q, k, v, kv_mask, causal, scale, block_q,
                                 block_k, interpret)


def _flash_fwd(q, k, v, kv_mask, causal, scale, block_q, block_k,
               bwd_block_q, bwd_block_k, interpret):
    out, lse = _flash_pallas_forward(q, k, v, kv_mask, causal, scale, block_q,
                                     block_k, interpret, with_lse=True)
    return out, (q, k, v, kv_mask, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, bwd_block_q, bwd_block_k,
               interpret, res, g):
    q, k, v, kv_mask, out, lse = res
    dq, dk, dv = _flash_pallas_backward(q, k, v, kv_mask, out, lse, g, causal,
                                        scale, bwd_block_q, bwd_block_k,
                                        interpret)
    return dq, dk, dv, None  # mask carries no gradient


_flash.defvjp(_flash_fwd, _flash_bwd)


def _auto_block(n: int, cap: int) -> int:
    """Largest power-of-two block <= cap that divides n (from 128 up).
    Sequences shorter than 128 get the sequence itself (the old
    ``min(128, s)`` clamp) so short-q cross-attention keeps the kernel."""
    if n < 128:
        return n
    b = 128
    while b * 2 <= cap and n % (b * 2) == 0:
        b *= 2
    return b


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    kv_mask=None):
    """Fused attention; [B,H,S,D] -> [B,H,S,D]. ``kv_mask`` is an optional
    [B, S_k] key-padding mask (1 = attend).

    Forward runs the pallas kernel on TPU when the sequence tiles cleanly
    (otherwise the jnp reference path — numerics match to fp tolerance).
    Backward goes through a custom VJP with its own pallas kernels: one,
    ``flash_bwd_dqkv``, that makes each probability tile once for dQ, dK and
    dV where a head's whole dQ fits its VMEM budget (rows up to 16 384 keys
    in bfloat16), and the ``flash_bwd_dq`` / ``flash_bwd_dkv`` pair past it;
    the shape decides, and ``record_attention_paths()`` says which.

    ``block_q``/``block_k`` default to an auto choice PER DIMENSION AND PATH:
    the forward kernel prefers the largest tiles that divide the sequence
    (up to 1024 — measured ~2x faster than 512x512 at seq 4096 on v5e),
    while the backward kernels prefer 512 x 512: four float32 tiles live at
    once, and under a causal mask a wider key tile computes more of the
    square above the diagonal. From 4096 keys on the fused kernel takes key
    tiles of 1024 (a tenth faster at 4096 keys on v5e, a fiftieth slower at
    2048: PERF.md section 6, PR 37). An explicitly passed value pins that
    dimension on BOTH paths; the other stays auto.
    """
    b, h, s, d = q.shape
    sk = k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    _user_block_q, _user_block_k = block_q, block_k  # pre-auto-derivation

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # p-tile is block_q*block_k f32: cap the product at 2^20 (4 MB VMEM)
    cap = 1024 if d <= 128 else 512
    bwd_cap_k = cap if _bwd_is_fused(s, d, q.dtype) and sk >= 4096 else 512
    bwd_block_q = min(block_q, s) if block_q is not None else _auto_block(s, 512)
    bwd_block_k = (min(block_k, sk) if block_k is not None
                   else _auto_block(sk, bwd_cap_k))
    block_q = min(block_q, s) if block_q is not None else _auto_block(s, cap)
    block_k = min(block_k, sk) if block_k is not None else _auto_block(sk, cap)
    # the XLA blockwise path materializes [B,H,S,block_k] f32 score blocks
    # in HBM — the pallas-tuned (VMEM-sized) auto block would inflate that
    # up to 8x, so the fallbacks cap at the scan's own tuned default
    xla_block_k = min(block_k, 512)
    if _FORCE_XLA.get():
        # explicit override (tests, callers that need the GSPMD-partitionable
        # form): blockwise unconditionally
        _note_path("flash_attention", "blockwise")
        return _blockwise_attention(q, k, v, kv_mask, causal, scale,
                                    block_k=xla_block_k)
    wrapped = _try_shardmap_flash(q, k, v, kv_mask, causal, scale, interpret,
                                  block_q=_user_block_q, block_k=_user_block_k)
    if wrapped is not None:
        return wrapped
    if _SHARD_ATTN.get() is not None:
        # sharded-jit trace but the shapes don't divide the mesh's
        # batch/heads axes (or the mesh has neither): the plain pallas call
        # would hand GSPMD an unpartitionable custom call — blockwise is the
        # partitionable form
        _note_path("flash_attention", "blockwise")
        return _blockwise_attention(q, k, v, kv_mask, causal, scale,
                                    block_k=xla_block_k)
    # TPU tiling: q-rows multiple of 8 (sublanes), k-cols multiple of 128
    # (lanes); sequences must tile exactly (pad upstream otherwise)
    tiles_ok = (s % block_q == 0 and sk % block_k == 0
                and s % bwd_block_q == 0 and sk % bwd_block_k == 0
                and block_q % 8 == 0 and block_k % 128 == 0
                and bwd_block_q % 8 == 0 and bwd_block_k % 128 == 0
                and d % 8 == 0)
    if not tiles_ok:
        _warn_reference(
            "flash_attention", q.shape, q.dtype,
            f"Sq={s} and Sk={sk} must tile into q-blocks {block_q}/"
            f"{bwd_block_q} (multiples of 8) and k-blocks {block_k}/"
            f"{bwd_block_k} (multiples of 128), and D={d} be a multiple of 8")
        if kv_mask is None:
            _note_path("flash_attention", "reference")
            return attention_reference(q, k, v, causal, scale)
        # blockwise keeps memory bounded when it tiles; its own fallback is
        # the dense reference path with the mask honored
        _note_path("flash_attention", "blockwise")
        return _blockwise_attention(q, k, v, kv_mask, causal, scale,
                                    block_k=xla_block_k)
    _note_path("flash_attention", "pallas")
    return _flash(q, k, v, kv_mask, causal, scale, block_q, block_k,
                  bwd_block_q, bwd_block_k, interpret)


# ---------------------------------------------------------------------------
# Paged attention (single-token decode over a page-table-indirected KV pool)
# ---------------------------------------------------------------------------


def _gather_dequant(pages, page_table, scales):
    """Gather pool pages per slot and (when quantized) apply the
    per-page-per-head scales: ``[num_pages, page, H, D]`` x ``[B, maxp]``
    -> ``[B, maxp*page, H, D]`` f32. The dequant convert runs on the
    GATHERED pages only — converting the whole pool is the GC-J108
    defect (it silently doubles peak pool memory)."""
    b, maxp = page_table.shape
    page, h, d = pages.shape[1:]
    g = pages[page_table].astype(jnp.float32)   # [B, maxp, page, H, D]
    if scales is not None:
        g = g * scales[page_table][:, :, None, :, None]
    return g.reshape(b, maxp * page, h, d)


def paged_attention_reference(q, k_pages, v_pages, page_table, lengths,
                              sm_scale: Optional[float] = None,
                              k_scales=None, v_scales=None):
    """Ground-truth decode attention over a paged KV pool, pure jnp.

    One query token per slot attends over that slot's cached keys/values,
    which live scattered across fixed-size pages of a shared pool:

    - ``q``: ``[B, H, D]`` — the current token's query per slot;
    - ``k_pages`` / ``v_pages``: ``[num_pages, page_size, H, D]`` pool;
    - ``page_table``: ``[B, max_pages]`` int32 — slot b's cache lives in
      pages ``page_table[b, :ceil(lengths[b]/page_size)]``, in order
      (entries past that count must still be valid pool indices — the
      manager points them at its scratch page);
    - ``lengths``: ``[B]`` int32 — valid tokens per slot; global position
      ``p * page_size + t < lengths[b]`` attends, everything else is
      masked. A slot with ``lengths == 0`` returns exact zeros.
    - ``k_scales`` / ``v_scales``: optional ``[num_pages, H]`` f32
      per-page-per-head dequantization scales for an int8/fp8 pool
      (``row = stored * scale``); pass both or neither.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    b, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    # gather (and dequantize) the slot's whole logical cache
    k = _gather_dequant(k_pages, page_table, k_scales)
    v = _gather_dequant(v_pages, page_table, v_scales)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), k,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    valid = pos[None, :] < lengths[:, None]               # [B, K]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, v)
    # all-masked rows softmax to uniform garbage; empty slots must be zeros
    out = jnp.where((lengths > 0)[:, None, None], out, 0.0)
    return out.astype(q.dtype)


# One K or V page block, padded to f32 (8, 128) tiles, may hold this many
# elements (1 MiB as f32). It is the only layout rule the v5e compiler has:
# any H, D, page size, pool dtype and verify width S under the bound lowers.
# Past it the kernels' f32 working set nears the 16 MiB scoped VMEM — the
# verify kernel over an f32 pool is refused at 2x this bound, every variant
# by 8x (RESOURCE_EXHAUSTED). tests/test_tpu_compile.py compiles both sides.
PAGED_BLOCK_LIMIT = 1 << 18


def _paged_block_rule(page: int, h: int, d: int) -> Optional[str]:
    """The rule a compiled paged kernel's layout breaks, or None."""
    padded = page * (-(-h // 8) * 8) * (-(-d // 128) * 128)
    if padded > PAGED_BLOCK_LIMIT:
        return (f"one [page={page}, H={h}, D={d}] block pads to {padded} "
                f"elements > PAGED_BLOCK_LIMIT={PAGED_BLOCK_LIMIT}")
    return None


def _paged_takes_reference(kernel: str, q, k_pages, interpret: bool) -> bool:
    """Decide and report one paged entry point's path: the reference under
    ``force_xla_attention()`` or past the block limit (interpret mode has no
    VMEM to overflow), the pallas kernel otherwise."""
    page, h, d = k_pages.shape[1:]
    rule = None if interpret else _paged_block_rule(page, h, d)
    if rule:
        _warn_reference(kernel, q.shape, k_pages.dtype, rule)
    reference = bool(rule) or _FORCE_XLA.get()
    _note_path(kernel, "reference" if reference else "pallas")
    return reference


def _scale_column(scales):
    """``[num_pages, H]`` scales as ``[num_pages, H, 1]``: a ``(1, H, 1)``
    block's last two dimensions equal the array's (any H is a legal block)
    and arrive with H on sublanes, the layout of the K/V page they scale."""
    return scales.astype(jnp.float32)[:, :, None]


def _load_page(ref, scale_ref):
    """One K or V page block as f32 ``[page, H, D]``. An int8/fp8 pool's
    ``[H, 1]`` per-page-per-head scale dequantizes it right here in VMEM,
    so no full-precision page exists beyond this one block."""
    x = ref[0].astype(jnp.float32)
    if scale_ref is not None:
        x = x * scale_ref[0]
    return x


def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  page_size: int, sm_scale: float):
    """Grid ``(B, max_pages)``; scalar-prefetched page table drives the
    K/V BlockSpec index maps, so program ``(b, p)`` sees slot b's p-th
    logical page already staged in VMEM. Online-softmax state (m, l, acc)
    folds across the slot's pages; pages at or past ``lengths[b]`` are
    skipped outright (no flops, state untouched). Over an int8/fp8 pool
    ``rest`` leads with the K and V scale refs: the page's ``[H, 1]`` scales
    ride the same index map (:func:`_load_page`)."""
    *scale_refs, o_ref, acc_ref, m_ref, l_ref = rest
    ks_ref, vs_ref = scale_refs or (None, None)
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(p * page_size < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # [H, D]
        k = _load_page(k_ref, ks_ref)                     # [page, H, D]
        v = _load_page(v_ref, vs_ref)
        # s[t, h] = q[h, :] . k[t, h, :]. One query row per head leaves the
        # left operand no free dimension, which Mosaic's matmul refuses —
        # and an M=1 matmul would idle the MXU anyway — so multiply and
        # reduce over the lanes; H stays on sublanes throughout.
        s = jnp.sum(k * q[None], axis=2, keepdims=True) * sm_scale
        tpos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(tpos < length, s, NEG_INF)          # ragged last page
        m_prev = m_ref[:]                                 # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        pexp = jnp.exp(s - m_new[None])                   # [page, H, 1]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(pexp, axis=0)
        # acc[h, d] += sum_t pexp[t, h] * v[t, h, d]
        acc_ref[:] = alpha * acc_ref[:] + jnp.sum(pexp * v, axis=0)
        m_ref[:] = m_new

    @pl.when(p == np_ - 1)
    def _finalize():
        # empty slot: init state (acc 0, l 0) divides to exact zeros
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, page_table, lengths,
                    sm_scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    k_scales=None, v_scales=None):
    """Decode attention kernel: one query token per slot against a
    page-table-indirected K/V pool. Same operands/semantics as
    :func:`paged_attention_reference` (which is its parity ground truth).

    The pallas grid is ``(B, max_pages)`` with the page table and lengths
    scalar-prefetched (``PrefetchScalarGridSpec``): the BlockSpec index map
    reads ``page_table[b, p]``, so the gather over scattered pages happens
    in the pipeline's DMA stage, not as a materialized ``[B, maxp*page]``
    cache copy the way the reference does it. Pages wholly past a slot's
    length cost no flops. A compiled kernel takes any head layout whose
    page block fits ``PAGED_BLOCK_LIMIT`` (GPT-2's 12 heads of 64 included);
    past it the reference runs instead, reported through
    ``last_attention_path`` and, on a TPU, a warning.

    With ``k_scales``/``v_scales`` (``[num_pages, H]`` f32) the pool is
    int8/fp8 and the kernel dequantizes inside the gather: the scale
    blocks ride the same scalar-prefetched page-table index map and scale
    the page block in VMEM — the full-precision pool is never materialized.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    b, h, d = q.shape
    page = k_pages.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if _paged_takes_reference("paged_attention", q, k_pages, interpret):
        return paged_attention_reference(q, k_pages, v_pages, page_table,
                                         lengths, sm_scale=scale,
                                         k_scales=k_scales,
                                         v_scales=v_scales)
    maxp = page_table.shape[1]
    page_spec = pl.BlockSpec((1, page, h, d),
                             lambda bb, p, t, l: (t[bb, p], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, h, d), lambda bb, p, t, l: (bb, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [q, k_pages, v_pages]
    if k_scales is not None:
        scale_spec = pl.BlockSpec((1, h, 1),
                                  lambda bb, p, t, l: (t[bb, p], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [_scale_column(k_scales), _scale_column(v_scales)]
    kernel = functools.partial(_paged_kernel, page_size=page, sm_scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d), lambda bb, p, t, l: (bb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),   # acc
            pltpu.VMEM((h, 1), jnp.float32),   # running max
            pltpu.VMEM((h, 1), jnp.float32),   # running sum
        ],
    )
    return pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        # the page axis folds one slot's online-softmax state — sequential
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)


# ---------------------------------------------------------------------------
# Paged verify attention (multi-query-position decode for speculative steps)
# ---------------------------------------------------------------------------


def paged_attention_verify_reference(q, k_pages, v_pages, page_table, start,
                                     sm_scale: Optional[float] = None,
                                     k_scales=None, v_scales=None):
    """Ground-truth multi-position decode attention over a paged KV pool.

    The speculative verify step scores ``S = k + 1`` consecutive positions
    per slot in one call: slot b's query ``s`` sits at absolute position
    ``start[b] + s`` and attends causally over everything at or before it.

    - ``q``: ``[B, H, S, D]`` — S consecutive query tokens per slot;
    - ``k_pages`` / ``v_pages``: ``[num_pages, page_size, H, D]`` pool, with
      the K/V for all S positions already written (the engine's attend
      scatters them before calling);
    - ``page_table``: ``[B, max_pages]`` int32, scratch-padded like
      :func:`paged_attention_reference`;
    - ``start``: ``[B]`` int32 — tokens committed *before* this chunk; query
      ``s`` attends positions ``<= start[b] + s``, so ``S == 1`` degenerates
      to :func:`paged_attention_reference` with ``lengths = start + 1``.
    - ``k_scales`` / ``v_scales``: optional ``[num_pages, H]`` f32
      per-page-per-head dequantization scales for an int8/fp8 pool.

    Every query attends at least itself, so there is no empty-slot case.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    b, h, s, d = q.shape
    page = k_pages.shape[1]
    maxp = page_table.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    k = _gather_dequant(k_pages, page_table, k_scales)
    v = _gather_dequant(v_pages, page_table, v_scales)
    att = jnp.einsum("bhsd,bkhd->bhsk", q.astype(jnp.float32), k,
                     preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(maxp * page, dtype=jnp.int32)
    qpos = start[:, None] + jnp.arange(s, dtype=jnp.int32)       # [B, S]
    valid = pos[None, None, :] <= qpos[:, :, None]               # [B, S, K]
    att = jnp.where(valid[:, None, :, :], att, NEG_INF)
    p = jax.nn.softmax(att, axis=-1)
    out = jnp.einsum("bhsk,bkhd->bhsd", p, v)
    return out.astype(q.dtype)


def _paged_verify_kernel(table_ref, start_ref, q_ref, k_ref, v_ref, *rest,
                         page_size: int, num_q: int, sm_scale: float):
    """Grid ``(B, max_pages)`` exactly like :func:`_paged_kernel`, but the
    online-softmax state carries ``num_q`` query rows per head and the
    validity mask is per-query causal (``tpos <= start[b] + s``)."""
    *scale_refs, o_ref, acc_ref, m_ref, l_ref = rest
    ks_ref, vs_ref = scale_refs or (None, None)
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    start = start_ref[b]

    @pl.when(p * page_size < start + num_q)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # [H, S, D]
        k = _load_page(k_ref, ks_ref)                     # [page, H, D]
        v = _load_page(v_ref, vs_ref)
        # att[h, s, t] = q[h, s, :] . k[t, h, :] (batch H, contract D)
        att = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (1,))),
                                  preferred_element_type=jnp.float32
                                  ) * sm_scale             # [H, S, page]
        tpos = p * page_size + jax.lax.broadcasted_iota(jnp.int32,
                                                        att.shape, 2)
        qpos = start + jax.lax.broadcasted_iota(jnp.int32, att.shape, 1)
        att = jnp.where(tpos <= qpos, att, NEG_INF)
        m_prev = m_ref[:]                                 # [H, S, 1]
        m_new = jnp.maximum(m_prev, jnp.max(att, axis=2, keepdims=True))
        pexp = jnp.exp(att - m_new)                       # [H, S, page]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(pexp, axis=2, keepdims=True)
        # acc[h, s, d] += sum_t pexp[h, s, t] * v[t, h, d]
        acc_ref[:] = alpha * acc_ref[:] + jax.lax.dot_general(
            pexp, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(p == np_ - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention_verify(q, k_pages, v_pages, page_table, start,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           k_scales=None, v_scales=None):
    """Speculative-verify attention kernel: ``S`` consecutive query positions
    per slot against the page-table-indirected K/V pool, per-query causal.
    Same operands/semantics as :func:`paged_attention_verify_reference`
    (its parity ground truth); same scalar-prefetch page-gather structure as
    :func:`paged_attention` — the grid just carries S query rows of
    online-softmax state instead of one. Pages wholly past ``start[b] + S``
    cost no flops. Any ``S`` compiles (``spec_k + 1`` as it comes); the
    reference runs only past ``PAGED_BLOCK_LIMIT``, reported via
    ``last_attention_path`` and, on a TPU, a warning.

    ``k_scales``/``v_scales`` (``[num_pages, H]`` f32) select the
    dequant-on-read kernel for an int8/fp8 pool, exactly like
    :func:`paged_attention`.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    b, h, s, d = q.shape
    page = k_pages.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if _paged_takes_reference("paged_attention_verify", q, k_pages,
                              interpret):
        return paged_attention_verify_reference(q, k_pages, v_pages,
                                                page_table, start,
                                                sm_scale=scale,
                                                k_scales=k_scales,
                                                v_scales=v_scales)
    maxp = page_table.shape[1]
    page_spec = pl.BlockSpec((1, page, h, d),
                             lambda bb, p, t, st: (t[bb, p], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, h, s, d), lambda bb, p, t, st: (bb, 0, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [q, k_pages, v_pages]
    if k_scales is not None:
        scale_spec = pl.BlockSpec((1, h, 1),
                                  lambda bb, p, t, st: (t[bb, p], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [_scale_column(k_scales), _scale_column(v_scales)]
    kernel = functools.partial(_paged_verify_kernel, page_size=page, num_q=s,
                               sm_scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, s, d),
                               lambda bb, p, t, st: (bb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, s, d), jnp.float32),   # acc
            pltpu.VMEM((h, s, 1), jnp.float32),   # running max
            pltpu.VMEM((h, s, 1), jnp.float32),   # running sum
        ],
    )
    return pl.pallas_call(
        kernel,
        name="paged_verify",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      *operands)


# ---------------------------------------------------------------------------
# Ring attention (sequence parallelism over a mesh axis)
# ---------------------------------------------------------------------------


def _block_stats(q, k, v, scale, causal, q_offset, k_offset, kv_mask=None):
    """One blockwise attention step -> (acc, m, l) in f32. [B,H,Sq,D]x[B,H,Sk,D]."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0) + q_offset
        ki = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1) + k_offset
        s = jnp.where(qi >= ki, s, NEG_INF)
    if kv_mask is not None:  # [B, Sk] key padding mask
        s = jnp.where(kv_mask[:, None, None, :] > 0, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                        # [B,H,Sq,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return acc, m, l


def _merge_stats(acc, m, l, a2, m2, l2):
    """Fold one blockwise (acc, max, sum) triple into the running online
    -softmax state — shared by ring attention and the flash backward."""
    m_new = jnp.maximum(m, m2)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(m2 - m_new)
    return acc * alpha + a2 * beta, m_new, l * alpha + l2 * beta


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None, kv_mask=None):
    """Attention where q/k/v are sequence-sharded over ``axis_name``.

    Must run inside ``shard_map`` (or pjit-of-shard_map) with q/k/v carrying
    the local sequence shard ``[B,H,S_local,D]``. K/V (and the optional
    ``kv_mask`` [B,S_local] key-padding mask) rotate around the ring;
    online-softmax stats merge per visit. Returns the local output shard.
    """
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    q_offset = idx * s_local

    perm = [(i, (i + 1) % n) for i in range(n)]
    have_mask = kv_mask is not None

    @jax.checkpoint
    def fold(acc, m, l, kc, vc, mc, k_offset):
        # remat per visit: backward recomputes the [S_local, S_local] block
        # instead of saving one per visit (which would rebuild the full
        # S_local x S_global score matrix ring attention exists to avoid)
        a2, m2, l2 = _block_stats(q, kc, vc, scale, causal, q_offset, k_offset,
                                  mc if have_mask else None)
        return _merge_stats(acc, m, l, a2, m2, l2)

    def body(step, carry):
        acc, m, l, kc, vc, mc = carry
        # the k/v block currently resident came from device (idx - step) % n
        src = (idx - step) % n
        acc, m_new, l = fold(acc, m, l, kc, vc, mc, src * s_local)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        if have_mask:
            mc = jax.lax.ppermute(mc, axis_name, perm)
        return acc, m_new, l, kc, vc, mc

    b, h, sl, _ = q.shape
    init = (jnp.zeros((b, h, sl, d), jnp.float32),
            jnp.full((b, h, sl, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sl, 1), jnp.float32),
            k, v,
            kv_mask if have_mask else jnp.zeros((b, sl), jnp.float32))
    acc, m, l, _, _, _ = jax.lax.fori_loop(0, n, body, init)
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_flash_attention(q, k, v, axis_name: str, causal: bool = False,
                         sm_scale: Optional[float] = None, kv_mask=None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: Optional[bool] = None):
    """Ring attention whose per-visit block compute is the PALLAS flash
    kernel (inside shard_map operands are device-local, so the kernel needs
    no partitioning rule — same principle as
    :func:`~sparkflow_tpu.parallel.dp.make_dp_shardmap_train_step`).

    The kernel's saved logsumexp makes cross-visit merging exact: visiting
    blocks combine as ``o = sum_i o_i * exp(lse_i - lse_total)`` with
    ``lse_total = logaddexp_i lse_i``. Causality with equal sequence shards
    reduces to three whole-block cases per visit — source shard strictly
    behind (full attention), same shard (locally-causal kernel, since the
    local diagonal IS the global diagonal), or strictly ahead (zero
    contribution) — so the kernel never needs global offsets.

    Falls back to :func:`ring_attention` when shapes don't satisfy the
    kernel's tiling constraints. The backward is ALSO a pallas ring: per
    visit the dq/dk/dv kernels recompute P from the forward's merged global
    logsumexp, and the dk/dv accumulators rotate with their k/v shard (see
    :func:`_ring_flash_backward`) — the kernel win covers training, not just
    the forward.
    """
    b, h, sl, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bq = min(block_q, sl)
    bk = min(block_k, sl)
    tiles_ok = (sl % bq == 0 and sl % bk == 0
                and bq % 8 == 0 and bk % 128 == 0 and d % 8 == 0)
    if not tiles_ok:
        _warn_reference(
            "ring_flash_attention", q.shape, q.dtype,
            f"the local sequence {sl} must tile into q-blocks {bq} "
            f"(multiple of 8) and k-blocks {bk} (multiple of 128), and "
            f"D={d} be a multiple of 8")
        return ring_attention(q, k, v, axis_name, causal=causal,
                              sm_scale=sm_scale, kv_mask=kv_mask)

    return _ring_flash(q, k, v, kv_mask, axis_name, causal, scale, bq, bk,
                       interpret)


def _ring_flash_forward(q, k, v, kv_mask, axis_name, causal, scale, bq, bk,
                        interpret, with_lse=False):
    b, h, sl, d = q.shape
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    have_mask = kv_mask is not None

    def visit(kc, vc, mc, local_causal):
        out, lse = _flash_pallas_forward(
            q, kc, vc, mc if have_mask else None, local_causal, scale,
            bq, bk, interpret, with_lse=True)
        return out.astype(jnp.float32), lse

    def body(step, carry):
        o, lse, kc, vc, mc = carry
        src = (idx - step) % n
        if causal:
            # three whole-block cases per visit (equal shards make the local
            # diagonal the global one): strictly-behind source -> full
            # attention; same shard -> locally-causal kernel; strictly-ahead
            # -> SKIPPED entirely (no kernel launch, zero contribution)
            branch = jnp.where(src == idx, 1, jnp.where(src > idx, 2, 0))
            o2, lse2 = jax.lax.switch(branch, [
                lambda: visit(kc, vc, mc, False),
                lambda: visit(kc, vc, mc, True),
                lambda: (jnp.zeros((b, h, sl, d), jnp.float32),
                         jnp.full((b, h, sl), NEG_INF, jnp.float32)),
            ])
        else:
            o2, lse2 = visit(kc, vc, mc, False)
        # exact merge via logsumexp weights
        lse_new = jnp.logaddexp(lse, lse2)                    # [B,H,S]
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + o2 * jnp.exp(lse2 - lse_new)[..., None])
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        if have_mask:
            mc = jax.lax.ppermute(mc, axis_name, perm)
        return o, lse_new, kc, vc, mc

    init = (jnp.zeros((b, h, sl, d), jnp.float32),
            jnp.full((b, h, sl), NEG_INF, jnp.float32),
            k, v,
            kv_mask if have_mask else jnp.zeros((b, sl), jnp.float32))
    o, lse, _, _, _ = jax.lax.fori_loop(0, n, body, init)
    if with_lse:
        return o.astype(q.dtype), lse
    return o.astype(q.dtype)


def _ring_flash_backward(q, k, v, kv_mask, out, lse, g, axis_name, causal,
                         scale, bq, bk, interpret):
    """Ring backward running the PALLAS dq/dk/dv kernels per visit.

    The forward's merged ``lse`` is the GLOBAL logsumexp for every local q row,
    so per-visit kernel calls with it recompute globally-normalized P blocks
    directly — each visit's dq/dk/dv contribution is exact, and contributions
    just sum. dk/dv accumulators ROTATE WITH their k/v shard: after n
    ppermutes they arrive home having collected every device's contribution.
    Same three-case causal structure as the forward (strictly-ahead sources
    contribute zero and skip the kernels entirely)."""
    b, h, sl, d = q.shape
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    have_mask = kv_mask is not None

    # q-side quantities (flat views + D = rowsum(dO*O)) never change across
    # visits — computed ONCE outside the ring loop
    qf, gf, lsef, delta = _flash_bwd_prep(q, out, lse, g)

    def visit(kc, vc, mc, local_causal):
        dq2, dk2, dv2 = _flash_pallas_backward_flat(
            qf, kc.reshape(b * h, sl, d), vc.reshape(b * h, sl, d), gf, lsef,
            delta, mc.astype(jnp.float32)[:, None, :] if have_mask else None,
            h, local_causal, scale, bq, bk, interpret)
        return (dq2.reshape(b, h, sl, d).astype(jnp.float32),
                dk2.reshape(b, h, sl, d).astype(jnp.float32),
                dv2.reshape(b, h, sl, d).astype(jnp.float32))

    def body(step, carry):
        dq, kc, vc, mc, dk, dv = carry
        src = (idx - step) % n
        if causal:
            branch = jnp.where(src == idx, 1, jnp.where(src > idx, 2, 0))
            dq2, dk2, dv2 = jax.lax.switch(branch, [
                lambda: visit(kc, vc, mc, False),
                lambda: visit(kc, vc, mc, True),
                lambda: (jnp.zeros((b, h, sl, d), jnp.float32),) * 3,
            ])
        else:
            dq2, dk2, dv2 = visit(kc, vc, mc, False)
        dq = dq + dq2
        dk = dk + dk2
        dv = dv + dv2
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        dk = jax.lax.ppermute(dk, axis_name, perm)
        dv = jax.lax.ppermute(dv, axis_name, perm)
        if have_mask:
            mc = jax.lax.ppermute(mc, axis_name, perm)
        return dq, kc, vc, mc, dk, dv

    zeros = jnp.zeros((b, h, sl, d), jnp.float32)
    init = (zeros, k, v,
            kv_mask if have_mask else jnp.zeros((b, sl), jnp.float32),
            zeros, zeros)
    dq, _, _, _, dk, dv = jax.lax.fori_loop(0, n, body, init)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ring_flash(q, k, v, kv_mask, axis_name, causal, scale, bq, bk, interpret):
    return _ring_flash_forward(q, k, v, kv_mask, axis_name, causal, scale,
                               bq, bk, interpret)


def _ring_flash_fwd(q, k, v, kv_mask, axis_name, causal, scale, bq, bk,
                    interpret):
    out, lse = _ring_flash_forward(q, k, v, kv_mask, axis_name, causal, scale,
                                   bq, bk, interpret, with_lse=True)
    return out, (q, k, v, kv_mask, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, bq, bk, interpret, res, g):
    # pallas dq/dk/dv kernels per ring visit (see _ring_flash_backward) — the
    # kernel win now covers the training path, not just the forward; memory
    # stays O(S/n) per device (lse + out residuals, per-visit recompute of P)
    q, k, v, kv_mask, out, lse = res
    dq, dk, dv = _ring_flash_backward(q, k, v, kv_mask, out, lse, g,
                                      axis_name, causal, scale, bq, bk,
                                      interpret)
    return dq, dk, dv, None  # mask carries no gradient


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)
