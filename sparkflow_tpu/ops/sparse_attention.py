"""Attention over a learned selection of keys: an indexer scores every
(query, key) pair, each query keeps its ``topk`` best keys, and the main
attention runs over those keys only.

- :func:`index_scores` / :func:`index_select`: the indexer's scores
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(heads * dim)`` and
  each query's ``topk`` largest among ``s <= t``, as a mask ``[B, S, S]``.
  Computed a block of queries at a time (all scores of one row of tokens in
  float32 are ``heads x S x S``); the ``topk``-th largest score of a query is
  found by bisection on the scores' bits, exactly, with no sort.
- :func:`selected_attention`: softmax attention of grouped query heads
  (``Hq`` query heads over ``Hkv`` key/value heads) under that mask: pallas
  kernels ``sparse_attn_fwd``, ``sparse_attn_bwd_dq``, ``sparse_attn_bwd_dkv``
  that mask tiles (a selection of scattered keys leaves hardly a tile empty:
  at 8 FLOP a gathered byte a per-query gather would be bound by memory at
  3 % of the MXU, a masked tile pays 2.3 x the selected pairs' operations at
  the MXU's own rate) and skip the tiles above the diagonal.
- :func:`selected_probs`: the attention's probabilities summed over the
  heads and normalised to one (kernel ``sparse_attn_probs``), the target of
  the indexer's loss.
- :func:`indexer_loss`: ``mean_t KL(P_t || softmax_{s in S_t} I[t, s])``,
  differentiable in the indexer's ``qI``, ``kI`` and ``w``.

Each stands beside a plain ``jnp`` reference (``*_reference``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_VMEM_LIMIT = 96 * 1024 * 1024
_INT_MIN = -2 ** 31


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


def kth_largest_key(keys, k: int):
    """The ``k``-th largest of each row of ``keys [T, S]`` (int32), built
    bit by bit from the top: 32 counts of a row, no sort."""
    def body(i, c):
        bit = 31 - i
        cand = jnp.where(bit == 31, jnp.zeros_like(c),
                         c | jnp.left_shift(jnp.int32(1), bit))
        enough = jnp.sum(keys >= cand[:, None], axis=-1) >= k
        return jnp.where(enough, cand, c)

    start = jnp.full((keys.shape[0],), _INT_MIN, jnp.int32)
    return jax.lax.fori_loop(0, 32, body, start)


def select_block(scores, first_row, topk: int):
    """``scores [T, S]`` of the queries ``first_row ..`` -> bool ``[T, S]``:
    each query's ``topk`` largest among ``s <= t`` (all of them where there
    are no more than ``topk``). Of the scores equal to the last one kept,
    the earliest keys are kept, as ``lax.top_k`` orders them: a relu leaves
    whole stretches of scores at exactly 0."""
    t = first_row + jnp.arange(scores.shape[0], dtype=jnp.int32)[:, None]
    causal = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :] <= t
    if topk >= scores.shape[1]:
        return causal
    # -0.0 and 0.0 are one score
    keys = jnp.where(causal, _sortable(scores + 0.0), _INT_MIN)
    kth = kth_largest_key(keys, topk)[:, None]
    above = keys > kth
    tied = causal & (keys == kth)
    room = topk - jnp.sum(above, axis=-1)
    return above | (tied & (_cols(scores) < _first_beyond(tied, room)[:, None]))


def _cols(a):
    return jnp.arange(a.shape[1], dtype=jnp.int32)[None, :]


def _first_beyond(flags, room):
    """For each row of ``flags [T, S]`` the column ``J`` such that exactly
    ``min(room, flags in the row)`` flags lie before it, built bit by bit (a
    count a bit: a cumulative sum along 8192 lanes costs more)."""
    cols = _cols(flags)
    bits = int(flags.shape[1]).bit_length()

    def body(i, j):
        cand = j | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        fits = jnp.sum(flags & (cols < cand[:, None]), axis=-1) <= room
        return jnp.where(fits, cand, j)

    return jax.lax.fori_loop(0, bits, body,
                             jnp.zeros((flags.shape[0],), jnp.int32))


def _blocks_of(a, block: int):
    """``a [S, ...]`` as ``[S / block, block, ...]``; one block where
    ``block`` does not divide ``S``."""
    s = a.shape[0]
    qb = block if block and s % block == 0 else s
    return a.reshape((s // qb, qb) + a.shape[1:])


def _kl_rows(scores, sel, p):
    """``KL(p[t] || softmax over sel[t] of scores[t])`` of each query."""
    logq = jax.nn.log_softmax(jnp.where(sel, scores, NEG_INF), axis=-1)
    logp = jnp.log(jnp.where(p > 0, p, 1.0))
    return jnp.sum(jnp.where(sel, p * (logp - logq), 0.0), axis=-1)


def index_select(qi, ki, w, topk: int, block: int = 256):
    """``qi [B, S, nh, d]``, ``ki [B, S, d]``, ``w [B, S, nh]`` -> the
    selection as ``int8 [B, S, S]`` (1 = query ``t`` attends key ``s``).
    No gradient: the selection is discrete."""
    qi, ki, w = jax.lax.stop_gradient((qi, ki, w))

    def row(qi_r, ki_r, w_r):
        s = qi_r.shape[0]
        qs, ws = _blocks_of(qi_r, block), _blocks_of(w_r, block)
        firsts = jnp.arange(0, s, qs.shape[1], dtype=jnp.int32)
        out = jax.lax.map(
            lambda a: select_block(index_scores(a[0], ki_r, a[1]), a[2],
                                   topk).astype(jnp.int8), (qs, ws, firsts))
        return out.reshape(s, s)

    return jax.vmap(row)(qi, ki, w)


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
    selection) carries no gradient; ``qi``, ``ki``, ``w`` do. A block of
    queries at a time, recomputed in the backward pass."""
    target = jax.lax.stop_gradient(target)

    def row(qi_r, ki_r, w_r, mask_r, target_r):
        @jax.checkpoint
        def blk(ki_r, a):
            q, ww, m, p = a
            return _kl_rows(index_scores(q, ki_r, ww), m != 0, p)

        kl = jax.lax.map(functools.partial(blk, ki_r), tuple(
            _blocks_of(a, block) for a in (qi_r, w_r, mask_r, target_r)))
        return jnp.mean(kl)

    return jax.vmap(row)(qi, ki, w, mask, target)


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


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                   dq_ref, acc_ref, *, sm_scale, group, block_q, block_k):
    qi, ki, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_visible(qi, ki, block_q, block_k))
    def _compute():
        k, v = k_ref[0], v_ref[0]
        sel = mask_ref[0] != 0
        for g in range(group):
            p = _p_tile(q_ref[0, g], k, lse_ref[0, g], sel, sm_scale)
            dp = jax.lax.dot_general(do_ref[0, g], v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, g])
            acc_ref[g] += sm_scale * jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, group,
                    block_q, block_k):
    ki, qi, nq = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_visible(qi, ki, block_q, block_k))
    def _compute():
        k, v = k_ref[0], v_ref[0]
        sel = mask_ref[0] != 0
        for g in range(group):
            q, do = q_ref[0, g], do_ref[0, g]
            p = _p_tile(q, k, lse_ref[0, g], sel, sm_scale)
            dv_acc[...] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, g])
            dk_acc[...] += sm_scale * jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _backward(q, k, v, mask, out, lse, g, scale, block_q, block_k, interpret):
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    shape_q = (b * hkv, group, s, d)
    qf, gf = q.reshape(shape_q), g.astype(q.dtype).reshape(shape_q)
    kf, vf = k.reshape(b * hkv, s, d), v.reshape(b * hkv, s, d)
    lsef = lse.reshape(b * hkv, group, s, 1)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(b * hkv, group, s, 1)
    common = dict(sm_scale=scale, group=group, block_q=block_q,
                  block_k=block_k)
    qspec, kspec, stat, mspec = _specs(group, block_q, block_k, d, hkv)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        name="sparse_attn_bwd_dq",
        grid=(b * hkv, s // block_q, s // block_k),
        in_specs=[qspec, kspec, kspec, qspec, stat, stat, mspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(shape_q, q.dtype),
        scratch_shapes=[pltpu.VMEM((group, block_q, d), jnp.float32)],
        compiler_params=_params(interpret, "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, delta, mask)
    qspec, kspec, stat, mspec = _specs(group, block_q, block_k, d, hkv, "kq")
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        name="sparse_attn_bwd_dkv",
        grid=(b * hkv, s // block_k, s // block_q),
        in_specs=[qspec, kspec, kspec, qspec, stat, stat, mspec],
        out_specs=(kspec, kspec),
        out_shape=(jax.ShapeDtypeStruct(kf.shape, k.dtype),
                   jax.ShapeDtypeStruct(vf.shape, v.dtype)),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_params(interpret, "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, delta, mask)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _selected(q, k, v, mask, scale, block_q, block_k, interpret):
    return _forward(q, k, v, mask, scale, block_q, block_k, interpret)


def _selected_fwd(q, k, v, mask, scale, block_q, block_k, interpret):
    out, lse = _forward(q, k, v, mask, scale, block_q, block_k, interpret)
    return (out, lse), (q, k, v, mask, out, lse)


def _selected_bwd(scale, block_q, block_k, interpret, res, g):
    q, k, v, mask, out, lse = res
    dq, dk, dv = _backward(q, k, v, mask, out, lse, g[0], scale, block_q,
                           block_k, interpret)
    return dq, dk, dv, None


_selected.defvjp(_selected_fwd, _selected_bwd)


def _block(s: int, cap: int = 512) -> int:
    """The largest of 512, 256, 128 that divides ``s``; a shorter or odd
    sequence is one block."""
    for b in (512, 256, 128):
        if b <= cap and s % b == 0:
            return b
    return s


def selected_attention(q, k, v, mask, sm_scale: Optional[float] = None,
                       block_q: Optional[int] = None,
                       block_k: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """Softmax attention of ``q [B, Hq, S, D]`` over the keys ``mask [B, S,
    S]`` (int8, 1 = attend; the selection already holds causality) selects
    for each query, ``k, v [B, Hkv, S, D]`` shared by ``Hq / Hkv`` query
    heads each. Returns ``(out, lse [B, Hq, S])``; the logsumexp carries no
    gradient (:func:`selected_probs` reads it). Every query has to select at
    least one key."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    s = q.shape[2]
    return _selected(q, k, v, mask.astype(jnp.int8), scale,
                     block_q or _block(s), block_k or _block(s), interpret)


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
