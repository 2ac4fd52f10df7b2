"""Attention over a learned selection of keys: an indexer scores every
(query, key) pair, each query keeps its ``topk`` best keys, and the main
attention runs over those keys only.

- :func:`index_select`: the indexer's scores ``I[t, s] = sum_j w[t, j]
  relu(qI[t, j] . kI[s]) / sqrt(heads * dim)`` and each query's ``topk``
  largest among ``s <= t``, as a mask ``[B, S, S]``: kernel ``index_select``.
  The scores never leave VMEM: a block of queries makes its visible tiles
  (:func:`_index_tile`; all scores of one row of tokens in float32 are
  ``heads x S x S``) into sortable int32 keys in a scratch ``[block, S]``,
  and at its last visible tile finds each query's ``topk``-th largest key
  by bisection on the keys' bits, exactly, with no sort, then the earliest
  keys among those equal to it (``lax.top_k``'s order), and writes the
  selection once. Tiles above the diagonal are neither fetched nor computed.
- :func:`selected_attention`: softmax attention of grouped query heads
  (``Hq`` query heads over ``Hkv`` key/value heads) under that mask: pallas
  kernels ``sparse_attn_fwd`` and ``sparse_attn_bwd_dqkv`` that mask tiles (a
  selection of scattered keys leaves hardly a tile empty: at 8 FLOP a
  gathered byte a per-query gather would be bound by memory at 3 % of the
  MXU, a masked tile pays 2.3 x the selected pairs' operations at the MXU's
  own rate) and skip the tiles above the diagonal. The backward kernel
  visits each tile once and makes its probabilities once a head
  (:func:`_bwd_tile`, which ``ops/block_attention.py`` runs too): dQ in a
  scratch of the query tile, dK and dV in float32 scratches of the KV head's
  whole row. Where those do not fit VMEM (:func:`_bwd_is_fused`, by the
  shape alone) the pair ``sparse_attn_bwd_dq``, ``sparse_attn_bwd_dkv`` runs
  the same tile function twice; ``record_attention_paths()`` of
  ``ops/attention.py`` holds ``sparse_attention_bwd:fused`` or ``:split``.
  The forward walks key tiles twice the backward's where the row allows
  (:func:`_tiles`: 512 x 1 024 against 512 x 512), and the log holds
  ``sparse_attention_fwd:<block_q>x<block_k>``.
- :func:`selected_probs`: the attention's probabilities summed over the
  heads and normalised to one (kernel ``sparse_attn_probs``), the target of
  the indexer's loss.
- :func:`indexer_loss`: ``mean_t KL(P_t || softmax_{s in S_t} I[t, s])``,
  differentiable in the indexer's ``qI``, ``kI`` and ``w``: kernels
  ``index_kl_fwd`` (an online logsumexp of the selected scores beside ``sum
  p (log p - I)``), ``index_kl_bwd_dq`` and ``index_kl_bwd_dk`` (the same
  tile made again, ``dI = g (softmax_sel(I) - p)``), in the shape of the
  attention kernels.

Each stands beside a plain ``jnp`` reference (``*_reference``;
:func:`index_scores` is theirs); on a CPU the kernels run interpreted.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _log_path

NEG_INF = -1e30
_VMEM_LIMIT = 96 * 1024 * 1024
_INT_MIN = -2 ** 31

# ``jax.ad_checkpoint.checkpoint_name``s of what the backward kernels read of
# their forward (``ATTN_*`` in ``_selected_fwd``, ``KL_*`` in
# ``_index_kl_fwd``; ``SELECTION`` is the caller's to put on the selection or
# its bits): a ``jax.checkpoint`` around a caller that keeps them with
# ``save_only_these_names`` runs no forward kernel again
# (``models/sparse_moe_lm.py``). Under no checkpoint a name does nothing.
SELECTION = "index_selection"
ATTN_OUT, ATTN_LSE = "sparse_attn_out", "sparse_attn_lse"
KL_LSE, KL_MASS = "index_kl_lse", "index_kl_mass"


# ---------------------------------------------------------------------------
# the indexer: scores, selection, loss
# ---------------------------------------------------------------------------


def index_scores(qi, ki, w):
    """``qi [T, nh, d]``, ``ki [S, d]``, ``w [T, nh]`` -> ``I [T, S]`` in
    float32 (no mask)."""
    nh, d = qi.shape[-2], qi.shape[-1]
    dots = jnp.einsum("tjd,sd->tjs", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("tjs,tj->ts", jax.nn.relu(dots),
                      w.astype(jnp.float32)) / math.sqrt(nh * d)


def _sortable(x):
    """float32 -> int32 whose order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _score_keys(scores, causal):
    """The scores as sortable keys, ``_INT_MIN`` off ``causal``; ``-0.0``
    and ``0.0`` are one score."""
    return jnp.where(causal, _sortable(scores + 0.0), _INT_MIN)


def kth_largest_key(at_least, k: int, rows: int):
    """The ``k``-th largest key (int32) of each of ``rows`` rows, as ``[rows,
    1]``, built bit by bit from the top: 32 counts of a row, no sort.
    ``at_least(c)`` counts each row's keys ``>= c [rows, 1]``. A row with
    fewer than ``k`` keys gives ``_INT_MIN``."""
    def body(i, c):
        bit = 31 - i
        cand = jnp.where(bit == 31, jnp.zeros_like(c),
                         c | jnp.left_shift(jnp.int32(1), bit))
        return jnp.where(at_least(cand) >= k, cand, c)

    return jax.lax.fori_loop(0, 32, body,
                             jnp.full((rows, 1), _INT_MIN, jnp.int32))


def _first_beyond(before, room, width: int):
    """For each row the column ``J [rows, 1]`` such that exactly ``min(room,
    flags in the row)`` flags lie before it, built bit by bit (a count a
    bit: a cumulative sum along 8192 lanes costs more). ``before(c)`` counts
    each row's flags in the columns ``< c [rows, 1]``, of ``width``."""
    bits = int(width).bit_length()

    def body(i, j):
        cand = j | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(before(cand) <= room, cand, j)

    return jax.lax.fori_loop(0, bits, body, jnp.zeros_like(room))


def _room(kth, above, topk: int):
    """How many of the keys equal to its ``kth`` a query keeps, ``above``
    being those beyond it. A query with fewer than ``topk`` keys has ``kth =
    _INT_MIN``, which only the keys off its causal part equal: none."""
    return jnp.where(kth == _INT_MIN, 0, topk - above)


def _count(flags):
    return jnp.sum(flags, axis=-1, keepdims=True, dtype=jnp.int32)


def select_block(scores, first_row, topk: int):
    """``scores [T, S]`` of the queries ``first_row ..`` -> bool ``[T, S]``:
    each query's ``topk`` largest among ``s <= t`` (all of them where there
    are no more than ``topk``). Of the scores equal to the last one kept,
    the earliest keys are kept, as ``lax.top_k`` orders them: a relu leaves
    whole stretches of scores at exactly 0. Plain ``jnp`` over whole rows;
    the kernel ``index_select`` runs the same functions over its tiles."""
    rows, width = scores.shape
    t = first_row + jnp.arange(rows, dtype=jnp.int32)[:, None]
    cols = jnp.arange(width, dtype=jnp.int32)[None, :]
    causal = cols <= t
    keys = _score_keys(scores, causal)
    kth = kth_largest_key(lambda c: _count(keys >= c), topk, rows)
    tied = keys == kth
    beyond = _first_beyond(lambda c: _count(tied & (cols < c)),
                           _room(kth, _count(keys > kth), topk), width)
    return (keys > kth) | (tied & (cols < beyond))


def _kl_rows(scores, sel, p):
    """``KL(p[t] || softmax over sel[t] of scores[t])`` of each query."""
    logq = jax.nn.log_softmax(jnp.where(sel, scores, NEG_INF), axis=-1)
    logp = jnp.log(jnp.where(p > 0, p, 1.0))
    return jnp.sum(jnp.where(sel, p * (logp - logq), 0.0), axis=-1)


def index_select(qi, ki, w, topk: int, block: int = 256):
    """``qi [B, S, nh, d]``, ``ki [B, S, d]``, ``w [B, S, nh]`` -> the
    selection as ``int8 [B, S, S]`` (1 = query ``t`` attends key ``s``):
    kernel ``index_select``, ``block`` queries a grid step (all of them
    where ``block`` does not divide ``S``). No gradient: the selection is
    discrete."""
    qi, ki, w = jax.lax.stop_gradient((qi, ki, w))
    return _select(*_index_layout(qi, ki, w), topk,
                   *_index_blocks(qi.shape[1], block))


def pack_selection(mask):
    """A selection ``int8 [B, S, S]`` of 0 and 1 as bits, ``uint8 [B, ceil(S
    / 8), S]`` (eight queries a byte, the keys' axis as it was): an eighth of
    the bytes for whoever keeps it from a forward pass to its backward."""
    return jnp.packbits(mask.astype(jnp.uint8), axis=1)


def unpack_selection(packed):
    """:func:`pack_selection` undone, to the bit."""
    return jnp.unpackbits(packed, axis=1, count=packed.shape[2]).astype(
        jnp.int8)


def index_select_reference(qi, ki, w, topk: int):
    """Plain ``jnp``: all scores at once and ``lax.top_k``'s indices."""
    def row(qi_r, ki_r, w_r):
        s = qi_r.shape[0]
        scores = index_scores(qi_r, ki_r, w_r)
        causal = jnp.tril(jnp.ones((s, s), bool))
        idx = jax.lax.top_k(jnp.where(causal, scores, NEG_INF),
                            min(topk, s))[1]
        picked = jnp.zeros((s, s), bool).at[
            jnp.arange(s)[:, None], idx].set(True)
        return (causal & picked).astype(jnp.int8)

    return jax.vmap(row)(qi, ki, w)


def indexer_loss(qi, ki, w, mask, target, block: int = 256):
    """``mean_t KL(target[t] || softmax_{s: mask[t, s]} I[t, s])`` of each
    row of tokens ``[B]``. ``target [B, S, S]`` (rows summing to one over the
    selection) carries no gradient; ``qi``, ``ki``, ``w`` do: kernel
    ``index_kl_fwd`` and, backward, ``index_kl_bwd_dq`` and
    ``index_kl_bwd_dk``, which make the scores' tiles again from the
    forward's logsumexp and mass of each query (named :data:`KL_LSE` and
    :data:`KL_MASS` for a checkpoint to keep). ``block`` queries a grid step,
    as in :func:`index_select`."""
    kl = _index_kl(*_index_layout(qi, ki, w), mask.astype(jnp.int8),
                   jax.lax.stop_gradient(target).astype(jnp.float32),
                   *_index_blocks(qi.shape[1], block))
    return jnp.mean(kl, axis=-1)


def indexer_loss_reference(qi, ki, w, mask, target):
    """Plain ``jnp``, all scores at once."""
    def row(qi_r, ki_r, w_r, m, p):
        return jnp.mean(_kl_rows(index_scores(qi_r, ki_r, w_r), m != 0, p))

    return jax.vmap(row)(qi, ki, w, mask, jax.lax.stop_gradient(target))


# ---------------------------------------------------------------------------
# attention over the selected keys
# ---------------------------------------------------------------------------


def _scores_reference(q, k, mask, scale):
    kq = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, kq,
                    preferred_element_type=jnp.float32) * scale
    return jnp.where(mask[:, None] != 0, sc, NEG_INF)


def selected_attention_reference(q, k, v, mask,
                                 sm_scale: Optional[float] = None):
    """Plain softmax attention under ``mask``: ``q [B, Hq, S, D]``, ``k, v
    [B, Hkv, S, D]`` (query head ``j`` reads KV head ``j // (Hq / Hkv)``),
    ``mask [B, S, S]``. Returns ``(out [B, Hq, S, D], lse [B, Hq, S])``."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    sc = _scores_reference(q, k, mask, scale)
    lse = jax.nn.logsumexp(sc, axis=-1)
    p = jnp.where(mask[:, None] != 0, jnp.exp(sc - lse[..., None]), 0.0)
    vq = jnp.repeat(v, q.shape[1] // v.shape[1], axis=1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), vq,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype), lse


def selected_probs_reference(q, k, lse, mask,
                             sm_scale: Optional[float] = None):
    """``[B, S, S]`` float32: the probabilities of all heads, summed and
    normalised to one over each query's selection."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    sc = _scores_reference(q, k, mask, scale)
    p = jnp.where(mask[:, None] != 0, jnp.exp(sc - lse[..., None]), 0.0)
    return jnp.mean(p, axis=1)


def _visible(qi, ki, block_q, block_k):
    """Is any key of tile ``ki`` at or below the diagonal of tile ``qi``."""
    return qi * block_q + block_q - 1 >= ki * block_k


def _last_tile(qi, block_q, block_k):
    return (qi * block_q + block_q - 1) // block_k


def _p_tile(q, k, lse, sel, sm_scale):
    """The normalised probabilities of one head's tile, 0 off the
    selection."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    return jnp.where(sel, jnp.exp(jnp.where(sel, s, NEG_INF) - lse), 0.0)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, group, block_q, block_k):
    qi, ki, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_visible(qi, ki, block_q, block_k))
    def _compute():
        k, v = k_ref[0], v_ref[0]
        sel = mask_ref[0] != 0
        for g in range(group):
            s = jax.lax.dot_general(
                q_ref[0, g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(sel, s, NEG_INF)
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(sel, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[g] = alpha * acc_ref[g] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        for g in range(group):
            l = jnp.maximum(l_ref[g], 1e-30)
            o_ref[0, g] = (acc_ref[g] / l).astype(o_ref.dtype)
            lse_ref[0, g] = m_ref[g] + jnp.log(l)


def _specs(group, block_q, block_k, d, hkv, order="qk"):
    """The BlockSpecs of ``q``-like ``[BH, G, S, D]``, ``k``-like ``[BH, S,
    D]``, row statistics ``[BH, G, S, 1]`` and the mask ``[B, S, S]`` for a
    grid ``(bh, qi, ki)`` (or ``(bh, ki, qi)``). A key tile above the
    diagonal is never read: its index is held at the last visible one, so
    nothing is fetched for it."""
    if order == "qk":
        pick = lambda f: (lambda bh, qi, ki: f(bh, qi, jnp.minimum(
            ki, _last_tile(qi, block_q, block_k))))
    else:
        pick = lambda f: (lambda bh, ki, qi: f(bh, jnp.maximum(
            qi, (ki * block_k) // block_q), ki))
    qspec = pl.BlockSpec((1, group, block_q, d),
                         pick(lambda bh, qi, ki: (bh, 0, qi, 0)))
    kspec = pl.BlockSpec((1, block_k, d),
                         pick(lambda bh, qi, ki: (bh, ki, 0)))
    stat = pl.BlockSpec((1, group, block_q, 1),
                        pick(lambda bh, qi, ki: (bh, 0, qi, 0)))
    mspec = pl.BlockSpec((1, block_q, block_k),
                         pick(lambda bh, qi, ki: (bh // hkv, qi, ki)))
    return qspec, kspec, stat, mspec


def _params(interpret, *semantics):
    # row statistics travel as [.., block_q, 1] blocks, which the (8, 128)
    # tiling pads to 128 lanes: eight heads of them pass the default 16 MB
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)


def _forward(q, k, v, mask, scale, block_q, block_k, interpret):
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    _log_path("sparse_attention_fwd", f"{block_q}x{block_k}")
    qf = q.reshape(b * hkv, group, s, d)
    kf, vf = k.reshape(b * hkv, s, d), v.reshape(b * hkv, s, d)
    qspec, kspec, stat, mspec = _specs(group, block_q, block_k, d, hkv)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=scale, group=group,
                          block_q=block_q, block_k=block_k),
        name="sparse_attn_fwd",
        grid=(b * hkv, s // block_q, s // block_k),
        in_specs=[qspec, kspec, kspec, mspec],
        out_specs=(qspec, stat),
        out_shape=(jax.ShapeDtypeStruct(qf.shape, q.dtype),
                   jax.ShapeDtypeStruct((b * hkv, group, s, 1), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((group, block_q, d), jnp.float32),
                        pltpu.VMEM((group, block_q, 1), jnp.float32),
                        pltpu.VMEM((group, block_q, 1), jnp.float32)],
        compiler_params=_params(interpret, "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(qf, kf, vf, mask)
    return out.reshape(b, hq, s, d), lse.reshape(b, hq, s)


def _bwd_tile(q_ref, k, v, do_ref, lse_ref, delta_ref, sel, sm_scale,
              dq_acc=None, dk_acc=None, dv_acc=None, rows=slice(None)):
    """One visited tile of the backward pass, the heads of the group in
    turn: a head's probabilities ``p``, ``dp = dO V^T`` and ``ds = p (dp -
    delta)`` are made once, and from them its terms of dQ (``scale dS K``,
    added to ``dq_acc[g]``), of dV (``P^T dO``) and of dK (``scale dS^T Q``),
    float32. The group's terms of dK and dV are summed first and added to
    ``rows`` of ``dk_acc`` and ``dv_acc`` once a tile (one pass over the
    accumulators in place of one a head: 3 % of the fused kernel's time on a
    v5e). A kernel that keeps one side only leaves the other side's
    accumulators out."""
    dk = dv = None
    for g in range(q_ref.shape[1]):
        q, do = q_ref[0, g], do_ref[0, g]
        p = _p_tile(q, k, lse_ref[0, g], sel, sm_scale)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, g])
        if dq_acc is not None:
            dq_acc[g] += sm_scale * jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        if dk_acc is not None:
            dv_g = jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_g = jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dv, dk = (dv_g, dk_g) if g == 0 else (dv + dv_g, dk + dk_g)
    if dk_acc is not None:
        dv_acc[rows] += dv
        dk_acc[rows] += sm_scale * dk


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                   dq_ref, acc_ref, *, sm_scale, block_q, block_k):
    qi, ki, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_visible(qi, ki, block_q, block_k))
    def _compute():
        _bwd_tile(q_ref, k_ref[0], v_ref[0], do_ref, lse_ref, delta_ref,
                  mask_ref[0] != 0, sm_scale, dq_acc=acc_ref)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, block_q,
                    block_k):
    ki, qi, nq = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_visible(qi, ki, block_q, block_k))
    def _compute():
        _bwd_tile(q_ref, k_ref[0], v_ref[0], do_ref, lse_ref, delta_ref,
                  mask_ref[0] != 0, sm_scale, dk_acc=dk_acc, dv_acc=dv_acc)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dqkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     mask_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                     *, sm_scale, block_q, block_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)

    @pl.when(ki == 0)
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    # dk_acc and dv_acc hold the KV head's whole row, all query tiles long
    @pl.when((qi == 0) & (ki == 0))
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_visible(qi, ki, block_q, block_k))
    def _compute():
        _bwd_tile(q_ref, k_ref[0], v_ref[0], do_ref, lse_ref, delta_ref,
                  mask_ref[0] != 0, sm_scale, dq_acc, dk_acc, dv_acc,
                  _chunk(ki, block_k))

    @pl.when(ki == nk - 1)
    def _finalize_dq():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)

    # the blocks of dK and dV follow bh alone: they leave VMEM after the KV
    # head's last grid step
    @pl.when((qi == nq - 1) & (ki == nk - 1))
    def _finalize_dkv():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# What the fused backward may keep in VMEM for one KV head's dK and dV: the
# two float32 accumulators and the two buffers of each output block, each
# ``[s, d]`` with ``d`` padded to the 128 lanes. 32 MiB holds 8 192 positions
# in bfloat16 (16 MiB) and in float32 (24 MiB) and 16 384 in bfloat16 at any
# ``d <= 128``. With the group's tiles, statistics and dQ the v5e's compiler
# allocates, at a group of 8 and tiles of 512 x 512: 34.8 MB for 8 192
# positions in bfloat16 (both MoE cells), under 50 MB in float32, under 52 MB
# for 16 384 in bfloat16 (of ``_VMEM_LIMIT``, of the chip's 128 MiB). The
# forward kernels hold no row: at the same shapes and their own tiles of 512 x
# 1 024 (:func:`_tiles`) the least limit the compiler takes is 36.1 MB for
# ``sparse_attn_fwd`` and 35.3 MB for ``block_attn_fwd`` (18.3 and 17.9 at
# 512 x 512, 49.8 and 47.8 at 512 x 2 048), of ``_VMEM_LIMIT``'s 100.7 MB.
_FUSED_DKV_VMEM_BUDGET = 32 * 1024 * 1024


def _bwd_is_fused(s: int, d: int, dtype) -> bool:
    """Does a KV head's row of dK and dV fit the fused backward's budget
    (``ops/block_attention.py`` asks too)."""
    lanes = -(-d // 128) * 128
    return (2 * s * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)
            <= _FUSED_DKV_VMEM_BUDGET)


def _row_spec(s, d):
    """A KV head's whole ``[s, d]`` of a ``k``-like ``[BH, S, D]``."""
    return pl.BlockSpec((1, s, d), lambda bh, *_: (bh, 0, 0))


def _backward(q, k, v, mask, out, lse, g, scale, block_q, block_k, interpret):
    """dQ, dK, dV: one kernel that visits each tile once where a KV head's
    dK and dV fit :data:`_FUSED_DKV_VMEM_BUDGET`, the dq and dkv kernels
    past it."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    shape_q = (b * hkv, group, s, d)
    qf, gf = q.reshape(shape_q), g.astype(q.dtype).reshape(shape_q)
    kf, vf = k.reshape(b * hkv, s, d), v.reshape(b * hkv, s, d)
    lsef = lse.reshape(b * hkv, group, s, 1)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(b * hkv, group, s, 1)
    fused = _bwd_is_fused(s, d, k.dtype)
    _log_path("sparse_attention_bwd", "fused" if fused else "split")
    dq, dk, dv = (_bwd_fused if fused else _bwd_split)(
        qf, kf, vf, gf, lsef, delta, mask, hkv, scale, block_q, block_k,
        interpret)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _bwd_fused(qf, kf, vf, gf, lsef, delta, mask, hkv, scale, block_q,
               block_k, interpret):
    bh, group, s, d = qf.shape
    qspec, kspec, stat, mspec = _specs(group, block_q, block_k, d, hkv)
    row = _row_spec(s, d)
    return pl.pallas_call(
        functools.partial(_bwd_dqkv_kernel, sm_scale=scale, block_q=block_q,
                          block_k=block_k),
        name="sparse_attn_bwd_dqkv",
        grid=(bh, s // block_q, s // block_k),
        in_specs=[qspec, kspec, kspec, qspec, stat, stat, mspec],
        out_specs=(qspec, row, row),
        out_shape=(jax.ShapeDtypeStruct(qf.shape, qf.dtype),
                   jax.ShapeDtypeStruct(kf.shape, kf.dtype),
                   jax.ShapeDtypeStruct(vf.shape, vf.dtype)),
        scratch_shapes=[pltpu.VMEM((group, block_q, d), jnp.float32),
                        pltpu.VMEM((s, d), jnp.float32),
                        pltpu.VMEM((s, d), jnp.float32)],
        # dK and dV sum over qi: only the (row, KV head) pairs are independent
        compiler_params=_params(interpret, "parallel", "arbitrary",
                                "arbitrary"),
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, delta, mask)


def _bwd_split(qf, kf, vf, gf, lsef, delta, mask, hkv, scale, block_q,
               block_k, interpret):
    bh, group, s, d = qf.shape
    common = dict(sm_scale=scale, block_q=block_q, block_k=block_k)
    qspec, kspec, stat, mspec = _specs(group, block_q, block_k, d, hkv)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        name="sparse_attn_bwd_dq",
        grid=(bh, s // block_q, s // block_k),
        in_specs=[qspec, kspec, kspec, qspec, stat, stat, mspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        scratch_shapes=[pltpu.VMEM((group, block_q, d), jnp.float32)],
        compiler_params=_params(interpret, "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, delta, mask)
    qspec, kspec, stat, mspec = _specs(group, block_q, block_k, d, hkv, "kq")
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        name="sparse_attn_bwd_dkv",
        grid=(bh, s // block_k, s // block_q),
        in_specs=[qspec, kspec, kspec, qspec, stat, stat, mspec],
        out_specs=(kspec, kspec),
        out_shape=(jax.ShapeDtypeStruct(kf.shape, kf.dtype),
                   jax.ShapeDtypeStruct(vf.shape, vf.dtype)),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_params(interpret, "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, delta, mask)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _selected(q, k, v, mask, scale, fwd_tile, bwd_tile, interpret):
    """``fwd_tile`` and ``bwd_tile``: the ``(block_q, block_k)`` of the
    forward kernel and of the backward's."""
    return _forward(q, k, v, mask, scale, *fwd_tile, interpret)


def _selected_fwd(q, k, v, mask, scale, fwd_tile, bwd_tile, interpret):
    out, lse = _forward(q, k, v, mask, scale, *fwd_tile, interpret)
    # the names sit on the values the backward kernels read: a checkpoint
    # that keeps them by name does not run ``sparse_attn_fwd`` again
    out, lse = checkpoint_name(out, ATTN_OUT), checkpoint_name(lse, ATTN_LSE)
    return (out, lse), (q, k, v, mask, out, lse)


def _selected_bwd(scale, fwd_tile, bwd_tile, interpret, res, g):
    q, k, v, mask, out, lse = res
    dq, dk, dv = _backward(q, k, v, mask, out, lse, g[0], scale, *bwd_tile,
                           interpret)
    return dq, dk, dv, None


_selected.defvjp(_selected_fwd, _selected_bwd)


def _block(s: int, cap: int = 512) -> int:
    """The largest of 1024, 512, 256, 128 up to ``cap`` that divides ``s``;
    a shorter or odd sequence is one block."""
    for b in (1024, 512, 256, 128):
        if b <= cap and s % b == 0:
            return b
    return s


def _tiles(s: int, block_q: Optional[int] = None,
           block_k: Optional[int] = None):
    """The ``(block_q, block_k)`` of a masked attention's forward kernel and
    of its backward's, for rows of ``s`` keys (``ops/block_attention.py``
    asks too, of its half row). Tiles a caller names are every kernel's. By
    default both are :func:`_block` of the row, and the forward's key tile
    one step wider, up to 1 024: a row of 1 024 or more that 1 024 divides
    walks 512 x 1 024 forward and 512 x 512 backward. The forward is bound by
    the online softmax's float32 vector work, done once a visit and head
    (scale, two selects, max, exp, sum, the rescaling of the group's
    accumulators), and twice the keys a visit halve the rescalings and the
    grid steps; the backwards have no running maximum and are at their best
    at 512 x 512."""
    block_q = block_q or _block(s)
    return ((block_q, block_k or _block(s, 1024)),
            (block_q, block_k or _block(s)))


def selected_attention(q, k, v, mask, sm_scale: Optional[float] = None,
                       block_q: Optional[int] = None,
                       block_k: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """Softmax attention of ``q [B, Hq, S, D]`` over the keys ``mask [B, S,
    S]`` (int8, 1 = attend; the selection already holds causality) selects
    for each query, ``k, v [B, Hkv, S, D]`` shared by ``Hq / Hkv`` query
    heads each. Returns ``(out, lse [B, Hq, S])``; the logsumexp carries no
    gradient (:func:`selected_probs` reads it). Every query has to select at
    least one key. The backward kernels read ``out`` and ``lse`` again: the
    forward of the ``custom_vjp`` names them :data:`ATTN_OUT` and
    :data:`ATTN_LSE` for a checkpoint to keep. ``block_q`` and ``block_k``
    are every kernel's tiles; by default :func:`_tiles` of the row."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _selected(q, k, v, mask.astype(jnp.int8), scale,
                     *_tiles(q.shape[2], block_q, block_k), interpret)


def _probs_kernel(q_ref, k_ref, lse_ref, mask_ref, p_ref, *, sm_scale, hkv,
                  group, block_q, block_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    visible = _visible(qi, ki, block_q, block_k)

    @pl.when(visible)
    def _compute():
        sel = mask_ref[0] != 0
        total = jnp.zeros(p_ref.shape[1:], jnp.float32)
        for h in range(hkv * group):
            total += _p_tile(q_ref[0, h], k_ref[0, h // group],
                             lse_ref[0, h], sel, sm_scale)
        p_ref[0] = total / (hkv * group)

    @pl.when(jnp.logical_not(visible))
    def _zero():
        p_ref[0] = jnp.zeros(p_ref.shape[1:], jnp.float32)


def selected_probs(q, k, lse, mask, sm_scale: Optional[float] = None,
                   block_q: Optional[int] = None,
                   block_k: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """``[B, S, S]`` float32: the attention's probabilities over each
    query's selection, summed over the heads and normalised to one, from the
    logsumexp :func:`selected_attention` returned. No gradient."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    block_q = block_q or _block(s, 128)
    block_k = block_k or _block(s)
    held = lambda f: (lambda bi, qi, ki: f(bi, qi, jnp.minimum(
        ki, _last_tile(qi, block_q, block_k))))
    return pl.pallas_call(
        functools.partial(_probs_kernel, sm_scale=scale, hkv=hkv,
                          group=hq // hkv, block_q=block_q, block_k=block_k),
        name="sparse_attn_probs",
        grid=(b, s // block_q, s // block_k),
        in_specs=[
            pl.BlockSpec((1, hq, block_q, d),
                         lambda bi, qi, ki: (bi, 0, qi, 0)),
            pl.BlockSpec((1, hkv, block_k, d),
                         held(lambda bi, qi, ki: (bi, 0, ki, 0))),
            pl.BlockSpec((1, hq, block_q, 1),
                         lambda bi, qi, ki: (bi, 0, qi, 0)),
            pl.BlockSpec((1, block_q, block_k),
                         held(lambda bi, qi, ki: (bi, qi, ki))),
        ],
        out_specs=pl.BlockSpec((1, block_q, block_k),
                               lambda bi, qi, ki: (bi, qi, ki)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=_params(interpret, "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(q, k, lse[..., None], mask.astype(jnp.int8))


# ---------------------------------------------------------------------------
# the indexer's kernels: a tile of scores, made and consumed in VMEM
# ---------------------------------------------------------------------------

# queries whose bisections run together: their counts stay in registers
_SELECT_ROWS = 128


def _index_blocks(s: int, block: int):
    """``(block_q, block_k, interpret)`` of the indexer's kernels: ``block``
    queries a grid step, all of them where it does not divide ``s``."""
    return (block if block and s % block == 0 else s, _block(s),
            jax.default_backend() != "tpu")


def _index_specs(nh, d, block_q, block_k, order="qk"):
    """:func:`_specs` for the indexer's one key head: ``qI [B, nh, S, d]``,
    ``kI [B, S, d]``, ``w [B, nh, S, 1]``, a tile of ``[B, S, S]`` and a
    statistic of a query ``[B, 1, S, 1]``."""
    return _specs(nh, block_q, block_k, d, 1, order) + (
        _specs(1, block_q, block_k, d, 1, order)[2],)


def _index_layout(qi, ki, w):
    """``qi``, ``ki``, ``w`` as the kernels take them: ``[B, nh, S, d]``,
    ``[B, S, d]`` and float32 ``[B, nh, S, 1]``."""
    return (jnp.transpose(qi, (0, 2, 1, 3)), ki,
            jnp.transpose(w.astype(jnp.float32), (0, 2, 1))[..., None])


def _index_tile(q_ref, k, w_ref, relus_ref=None):
    """One tile of the index scores, ``I [bq, bk]`` float32, from the blocks
    ``q_ref [1, nh, bq, d]`` and ``w_ref [1, nh, bq, 1]`` and ``k [bk, d]``:
    each head's dots on the MXU with float32 accumulation; relu, weights and
    the sum over the heads, in their order, in float32. ``relus_ref [nh, bq,
    bk]`` keeps each head's relu for a backward pass."""
    nh, _, d = q_ref.shape[1:]
    total = None
    for j in range(nh):
        r = jnp.maximum(jax.lax.dot_general(
            q_ref[0, j], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), 0.0)
        if relus_ref is not None:
            relus_ref[j] = r
        term = r * w_ref[0, j]
        total = term if total is None else total + term
    return total / math.sqrt(nh * d)


def _chunk(c, block_k):
    return pl.ds(pl.multiple_of(c * block_k, block_k), block_k)


def _select_kernel(q_ref, k_ref, w_ref, sel_ref, keys_ref, *, topk, block_q,
                   block_k, rows):
    qi, ki, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    last = _last_tile(qi, block_q, block_k)
    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    @pl.when(ki <= last)
    def _scores():
        t = qi * block_q + iota((block_q, block_k), 0)
        cols = ki * block_k + iota((block_q, block_k), 1)
        scores = _index_tile(q_ref, k_ref[0], w_ref)
        keys_ref[:, _chunk(ki, block_k)] = _score_keys(scores, cols <= t)

    @pl.when(ki == last)
    def _select():
        def unseen(c, _):
            sel_ref[0, :, _chunk(c, block_k)] = jnp.zeros(
                (block_q, block_k), jnp.int8)
            return 0

        jax.lax.fori_loop(last + 1, nk, unseen, 0)
        # a count adds 128 lanes at a time and crosses them once, at its end
        lanes = 128 if block_k % 128 == 0 else block_k

        def group(g, _):
            mine = pl.ds(pl.multiple_of(g * rows, rows), rows)
            lane = iota((rows, block_k), 1)

            def count(flags):
                """``flags(keys, cols)`` counted over the visible keys."""
                def body(c, acc):
                    f = flags(keys_ref[mine, _chunk(c, block_k)],
                              c * block_k + lane).astype(jnp.int32)
                    return acc + sum(f[:, i:i + lanes]
                                     for i in range(0, block_k, lanes))

                return _count(jax.lax.fori_loop(
                    0, last + 1, body, jnp.zeros((rows, lanes), jnp.int32)))

            kth = kth_largest_key(
                lambda c: count(lambda keys, cols: keys >= c), topk, rows)
            beyond = _first_beyond(
                lambda c: count(lambda keys, cols:
                                (keys == kth) & (cols < c)),
                _room(kth, count(lambda keys, cols: keys > kth), topk),
                sel_ref.shape[2])

            def write(c, _):
                keys = keys_ref[mine, _chunk(c, block_k)]
                cols = c * block_k + lane
                kept = (keys > kth) | ((keys == kth) & (cols < beyond))
                sel_ref[0, mine, _chunk(c, block_k)] = kept.astype(jnp.int8)
                return 0

            return jax.lax.fori_loop(0, last + 1, write, 0)

        jax.lax.fori_loop(0, block_q // rows, group, 0)


def _select(q, k, w, topk, block_q, block_k, interpret):
    b, nh, s, d = q.shape
    qspec, kspec, wspec, _, _ = _index_specs(nh, d, block_q, block_k)
    rows = _SELECT_ROWS if block_q % _SELECT_ROWS == 0 else block_q
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, block_q=block_q,
                          block_k=block_k, rows=rows),
        name="index_select",
        grid=(b, s // block_q, s // block_k),
        in_specs=[qspec, kspec, wspec],
        out_specs=pl.BlockSpec((1, block_q, s), lambda bi, qi, ki: (bi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.int8),
        scratch_shapes=[pltpu.VMEM((block_q, s), jnp.int32)],
        compiler_params=_params(interpret, "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(q, k, w)


def _kl_fwd_kernel(q_ref, k_ref, w_ref, mask_ref, p_ref, kl_ref, lse_ref,
                   mass_ref, m_ref, l_ref, a_ref, *, block_q, block_k):
    qi, ki, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        a_ref[...] = jnp.zeros_like(a_ref)
        mass_ref[...] = jnp.zeros_like(mass_ref)

    @pl.when(_visible(qi, ki, block_q, block_k))
    def _compute():
        scores = _index_tile(q_ref, k_ref[0], w_ref)
        sel, p = mask_ref[0] != 0, p_ref[0]
        s = jnp.where(sel, scores, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        l_ref[...] = jnp.exp(m_prev - m_new) * l_ref[...] + rowsum(
            jnp.where(sel, jnp.exp(s - m_new), 0.0))
        m_ref[...] = m_new
        logp = jnp.log(jnp.where(p > 0, p, 1.0))
        a_ref[...] += rowsum(jnp.where(sel, p * (logp - scores), 0.0))
        mass_ref[0, 0] += rowsum(jnp.where(sel, p, 0.0))

    @pl.when(ki == nk - 1)
    def _finalize():
        # sum p (log p - I + lse), the logsumexp known only now
        lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        lse_ref[0, 0] = lse
        kl_ref[0, 0] = a_ref[...] + lse * mass_ref[0, 0]


def _kl_dtile(q_ref, k, w_ref, mask_ref, p_ref, lse_ref, mass_ref, g_ref,
              relus_ref):
    """The scores' tile again and ``g dKL / d(sum over the heads)`` of it:
    ``g (mass softmax_sel(I) - p)`` over the scale :func:`_index_tile`
    divides by. Leaves each head's relu in ``relus_ref``."""
    nh, d = q_ref.shape[1], q_ref.shape[3]
    scores = _index_tile(q_ref, k, w_ref, relus_ref)
    sel = mask_ref[0] != 0
    soft = jnp.exp(jnp.where(sel, scores, NEG_INF) - lse_ref[0, 0])
    return jnp.where(sel, g_ref[0, 0] * (mass_ref[0, 0] * soft - p_ref[0]),
                     0.0) / math.sqrt(nh * d)


def _kl_bwd_dq_kernel(q_ref, k_ref, w_ref, mask_ref, p_ref, lse_ref, mass_ref,
                      g_ref, dq_ref, dw_ref, dq_acc, dw_acc, relus_ref, *,
                      block_q, block_k):
    qi, ki, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(_visible(qi, ki, block_q, block_k))
    def _compute():
        k = k_ref[0]
        di = _kl_dtile(q_ref, k, w_ref, mask_ref, p_ref, lse_ref, mass_ref,
                       g_ref, relus_ref)
        for j in range(relus_ref.shape[0]):
            r = relus_ref[j]
            dw_acc[j] += jnp.sum(di * r, axis=1, keepdims=True)
            ddots = jnp.where(r > 0, di * w_ref[0, j], 0.0)
            dq_acc[j] += jax.lax.dot_general(
                ddots.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
        dw_ref[0] = dw_acc[...]


def _kl_bwd_dk_kernel(q_ref, k_ref, w_ref, mask_ref, p_ref, lse_ref, mass_ref,
                      g_ref, dk_ref, dk_acc, relus_ref, *, block_q, block_k):
    ki, qi, nq = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)

    @pl.when(_visible(qi, ki, block_q, block_k))
    def _compute():
        di = _kl_dtile(q_ref, k_ref[0], w_ref, mask_ref, p_ref, lse_ref,
                       mass_ref, g_ref, relus_ref)
        for j in range(relus_ref.shape[0]):
            q = q_ref[0, j]
            ddots = jnp.where(relus_ref[j] > 0, di * w_ref[0, j], 0.0)
            dk_acc[...] += jax.lax.dot_general(
                ddots.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)


def _kl_forward(q, k, w, mask, target, block_q, block_k, interpret):
    b, nh, s, d = q.shape
    qspec, kspec, wspec, tile, stat = _index_specs(nh, d, block_q, block_k)
    row = jax.ShapeDtypeStruct((b, 1, s, 1), jnp.float32)
    kl, lse, mass = pl.pallas_call(
        functools.partial(_kl_fwd_kernel, block_q=block_q, block_k=block_k),
        name="index_kl_fwd",
        grid=(b, s // block_q, s // block_k),
        in_specs=[qspec, kspec, wspec, tile, tile],
        out_specs=(stat, stat, stat),
        out_shape=(row, row, row),
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32)] * 3,
        compiler_params=_params(interpret, "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(q, k, w, mask, target)
    return kl.reshape(b, s), lse, mass


def _kl_backward(q, k, w, mask, target, lse, mass, g, block_q, block_k,
                 interpret):
    b, nh, s, d = q.shape
    args = (q, k, w, mask, target, lse, mass,
            g.astype(jnp.float32).reshape(b, 1, s, 1))
    relus = pltpu.VMEM((nh, block_q, block_k), jnp.float32)
    qspec, kspec, wspec, tile, stat = _index_specs(nh, d, block_q, block_k)
    dq, dw = pl.pallas_call(
        functools.partial(_kl_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k),
        name="index_kl_bwd_dq",
        grid=(b, s // block_q, s // block_k),
        in_specs=[qspec, kspec, wspec, tile, tile, stat, stat, stat],
        out_specs=(qspec, wspec),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(w.shape, w.dtype)),
        scratch_shapes=[pltpu.VMEM((nh, block_q, d), jnp.float32),
                        pltpu.VMEM((nh, block_q, 1), jnp.float32), relus],
        compiler_params=_params(interpret, "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(*args)
    qspec, kspec, wspec, tile, stat = _index_specs(nh, d, block_q, block_k,
                                                   "kq")
    dk = pl.pallas_call(
        functools.partial(_kl_bwd_dk_kernel, block_q=block_q,
                          block_k=block_k),
        name="index_kl_bwd_dk",
        grid=(b, s // block_k, s // block_q),
        in_specs=[qspec, kspec, wspec, tile, tile, stat, stat, stat],
        out_specs=kspec,
        out_shape=jax.ShapeDtypeStruct(k.shape, k.dtype),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32), relus],
        compiler_params=_params(interpret, "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(*args)
    return dq, dk, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _index_kl(q, k, w, mask, target, block_q, block_k, interpret):
    """Each query's ``KL(target || softmax over mask of I)``, ``[B, S]``,
    in the layout of :func:`_index_layout`."""
    return _kl_forward(q, k, w, mask, target, block_q, block_k, interpret)[0]


def _index_kl_fwd(q, k, w, mask, target, block_q, block_k, interpret):
    kl, lse, mass = _kl_forward(q, k, w, mask, target, block_q, block_k,
                                interpret)
    # as in ``_selected_fwd``: kept by name, ``index_kl_fwd`` runs once
    lse, mass = checkpoint_name(lse, KL_LSE), checkpoint_name(mass, KL_MASS)
    return kl, (q, k, w, mask, target, lse, mass)


def _index_kl_bwd(block_q, block_k, interpret, res, g):
    return _kl_backward(*res, g, block_q, block_k, interpret) + (None, None)


_index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)
