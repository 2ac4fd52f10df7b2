"""Attention under the mask of block-diffusion training: a row holds its
``length`` clean tokens and then their noised copy, and which keys a query
sees is a rule of the two indices, not causality and not an operand.

For an index ``p`` in ``[0, 2 * length)``: ``pos(p) = p mod length``,
``blk(p) = pos(p) // block``, and ``p`` is *clean* below ``length``, *noised*
from there. Query ``p`` sees key ``s`` when

- ``p`` clean: ``s`` clean and ``blk(s) <= blk(p)`` (causal by blocks, whole
  inside a block);
- ``p`` noised: ``s`` clean and ``blk(s) < blk(p)``, or ``s`` noised and
  ``blk(s) == blk(p)``.

``length^2 + length * block`` pairs of the ``(2 * length)^2`` square are
visible: a quarter of it, half of its causal triangle.

:func:`block_attention` is softmax attention of grouped query heads (``Hq``
query heads over ``Hkv`` key/value heads) under that rule: pallas kernels
``block_attn_fwd`` and ``block_attn_bwd_dqkv``. A kernel's grid runs over the
tiles the rule leaves something in and over no other (:func:`tile_schedule`:
for a clean query tile the clean key tiles up to its own, for a noised one
also the noised tile on its diagonal), so an empty tile is neither fetched
nor computed; the mask of a visited tile is made in the kernel from the
tile's two index ranges. One grid step holds the query heads of one KV head,
as in ``ops/sparse_attention.py``, whose backward tile (``_bwd_tile``: a
head's probabilities made once, its terms of dQ, dK and dV from them) and
whose choice of the backward (``_bwd_is_fused``: one kernel with the KV
head's whole dK and dV in VMEM where they fit, the pair ``block_attn_bwd_dq``,
``block_attn_bwd_dkv`` past that) this file shares;
``record_attention_paths()`` holds ``block_attention_bwd:fused`` or ``:split``.
The forward's key tile is its own too (``_tiles``: 512 x 1 024 against
the backward's 512 x 512 where the half row allows), and the log holds
``block_attention_fwd:<block_q>x<block_k>``.

:func:`visible` and :func:`block_attention_reference` are the rule and the
attention in plain ``jnp``; on a CPU the kernels run interpreted.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _log_path
from .sparse_attention import (NEG_INF, _bwd_is_fused, _bwd_tile,
                               _chunk, _params, _row_spec, _tiles)

# ``checkpoint_name``s of what the backward kernels read of the forward: a
# ``jax.checkpoint`` that keeps them runs ``block_attn_fwd`` no second time
# (``models/block_diffusion_lm.py``). Under no checkpoint a name does nothing.
ATTN_OUT, ATTN_LSE = "block_attn_out", "block_attn_lse"


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------


def positions(length: int):
    """``pos(p)`` of the ``2 * length`` indices of a row, int32."""
    return jnp.arange(2 * length, dtype=jnp.int32) % length


def visible(length: int, block: int):
    """The rule as ``bool [2 * length, 2 * length]`` (query, key)."""
    p = jnp.arange(2 * length)
    clean, blk = p < length, (p % length) // block
    qc, kc = clean[:, None], clean[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return jnp.where(qc, kc & (kb <= qb),
                     jnp.where(kc, kb < qb, kb == qb))


def visible_pairs(length: int, block: int) -> int:
    """How many (query, key) pairs of a row the rule leaves."""
    return length * length + length * block


def block_attention_reference(q, k, v, length: int, block: int,
                              sm_scale: Optional[float] = None):
    """Plain softmax attention under :func:`visible`: ``q [B, Hq, 2 length,
    D]``, ``k, v [B, Hkv, 2 length, D]`` (query head ``j`` reads KV head ``j
    // (Hq / Hkv)``). Returns ``(out, lse [B, Hq, 2 length])``."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    group = q.shape[1] // k.shape[1]
    mask = visible(length, block)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, group, axis=1),
                    preferred_element_type=jnp.float32) * scale
    sc = jnp.where(mask, sc, NEG_INF)
    lse = jax.nn.logsumexp(sc, axis=-1)
    p = jnp.where(mask, jnp.exp(sc - lse[..., None]), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype),
                     jnp.repeat(v, group, axis=1),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype), lse


def _tile_visible(q0, q1, k0, k1, length, block) -> bool:
    """Does the rule leave a pair in the tile of the queries ``[q0, q1)`` and
    the keys ``[k0, k1)``; neither range crosses ``length``."""
    blk = lambda i: (i % length) // block
    if k0 >= length:                       # noised keys: the own block only
        return (q0 >= length and blk(k0) <= blk(q1 - 1)
                and blk(q0) <= blk(k1 - 1))
    if q0 < length:
        return blk(k0) <= blk(q1 - 1)
    return blk(k0) < blk(q1 - 1)


def tile_schedule(length: int, block: int, block_q: int, block_k: int,
                  order: str = "qk") -> np.ndarray:
    """The tiles a kernel visits, in its order, as ``int32 [4, n]``: the
    query tile, the key tile, and whether the visit is the first and the last
    of its run (``order`` ``"qk"``: a query tile's key tiles one after
    another, for the forward and dQ; ``"kq"``: a key tile's query tiles, for
    dK/dV). Every tile the rule leaves a pair in is there once, and no
    other."""
    if length % block_q or length % block_k:
        raise ValueError(f"tiles of {block_q} x {block_k} do not divide a "
                         f"half row of {length}")
    nq, nk = 2 * length // block_q, 2 * length // block_k
    seen = [(qi, ki) for qi in range(nq) for ki in range(nk)
            if _tile_visible(qi * block_q, (qi + 1) * block_q,
                             ki * block_k, (ki + 1) * block_k, length, block)]
    run = 0 if order == "qk" else 1
    if run:
        seen.sort(key=lambda t: (t[1], t[0]))
    ids = [t[run] for t in seen]
    first = [i == 0 or ids[i - 1] != t for i, t in enumerate(ids)]
    last = first[1:] + [True]
    return np.array([[t[0] for t in seen], [t[1] for t in seen], first, last],
                    np.int32)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _rule_tile(qi, ki, block_q, block_k, length, block):
    """The rule on tile ``(qi, ki)``, ``bool [block_q, block_k]``, from the
    indices alone. The block numbers are taken on a column of queries and a
    row of keys (by a shift: ``block`` is a power of two); only two
    comparisons and an ``or`` run over the tile."""
    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    blk = lambda i: jnp.right_shift(i, block.bit_length() - 1)
    p = qi * block_q + iota((block_q, 1), 0)
    s = ki * block_k + iota((1, block_k), 1)
    p_clean, s_clean = p < length, s < length
    bp = blk(jnp.where(p_clean, p, p - length))
    bs = blk(jnp.where(s_clean, s, s - length))
    # a clean key is seen when blk(s) <= blk(p) by a clean query, < by a
    # noised one; a noised key by the noised queries of its own block. (A
    # select between two masks is not the compiler's: each side is a
    # comparison that the other kind of key can never pass.)
    below = bp + p_clean.astype(jnp.int32)
    own = jnp.where(p_clean, -1, bp)
    return ((jnp.where(s_clean, bs, jnp.int32(2 ** 30)) < below)
            | (jnp.where(s_clean, -2, bs) == own))


def _fwd_kernel(qt_ref, kt_ref, first_ref, last_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, acc_ref, m_ref, l_ref, *, sm_scale, group,
                rule):
    t = pl.program_id(1)

    @pl.when(first_ref[t] == 1)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k, v = k_ref[0], v_ref[0]
    sel = rule(qt_ref[t], kt_ref[t])
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

    @pl.when(last_ref[t] == 1)
    def _finalize():
        for g in range(group):
            l = jnp.maximum(l_ref[g], 1e-30)
            o_ref[0, g] = (acc_ref[g] / l).astype(o_ref.dtype)
            lse_ref[0, g] = m_ref[g] + jnp.log(l)


def _bwd_dq_kernel(qt_ref, kt_ref, first_ref, last_ref, q_ref, k_ref, v_ref,
                   do_ref, lse_ref, delta_ref, dq_ref, acc_ref, *, sm_scale,
                   rule):
    t = pl.program_id(1)

    @pl.when(first_ref[t] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _bwd_tile(q_ref, k_ref[0], v_ref[0], do_ref, lse_ref, delta_ref,
              rule(qt_ref[t], kt_ref[t]), sm_scale, dq_acc=acc_ref)

    @pl.when(last_ref[t] == 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(qt_ref, kt_ref, first_ref, last_ref, q_ref, k_ref, v_ref,
                    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc,
                    dv_acc, *, sm_scale, rule):
    t = pl.program_id(1)

    @pl.when(first_ref[t] == 1)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    _bwd_tile(q_ref, k_ref[0], v_ref[0], do_ref, lse_ref, delta_ref,
              rule(qt_ref[t], kt_ref[t]), sm_scale, dk_acc=dk_acc,
              dv_acc=dv_acc)

    @pl.when(last_ref[t] == 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dqkv_kernel(qt_ref, kt_ref, first_ref, last_ref, q_ref, k_ref, v_ref,
                     do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                     dq_acc, dk_acc, dv_acc, *, sm_scale, rule):
    t, visits = pl.program_id(1), pl.num_programs(1)

    @pl.when(first_ref[t] == 1)
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    # dk_acc and dv_acc hold the KV head's whole row, all visits long
    @pl.when(t == 0)
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    _bwd_tile(q_ref, k_ref[0], v_ref[0], do_ref, lse_ref, delta_ref,
              rule(qt_ref[t], kt_ref[t]), sm_scale, dq_acc, dk_acc, dv_acc,
              _chunk(kt_ref[t], k_ref.shape[1]))

    @pl.when(last_ref[t] == 1)
    def _finalize_dq():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)

    @pl.when(t == visits - 1)
    def _finalize_dkv():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _specs(group, block_q, block_k, d):
    """BlockSpecs of ``q``-like ``[BH, G, S, D]``, ``k``-like ``[BH, S, D]``
    and row statistics ``[BH, G, S, 1]`` for a grid ``(bh, visit)``: the
    schedule's tables say which tile a visit is."""
    qspec = pl.BlockSpec((1, group, block_q, d),
                         lambda bh, t, qt, kt, *_: (bh, 0, qt[t], 0))
    kspec = pl.BlockSpec((1, block_k, d),
                         lambda bh, t, qt, kt, *_: (bh, kt[t], 0))
    stat = pl.BlockSpec((1, group, block_q, 1),
                        lambda bh, t, qt, kt, *_: (bh, 0, qt[t], 0))
    return qspec, kspec, stat


def _call(kernel, name, schedule, in_specs, out_specs, out_shape, scratch,
          interpret, operands):
    """One kernel over the visits of ``schedule`` for every (row, KV head) of
    ``operands``' leading axis."""
    return pl.pallas_call(
        kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(operands[0].shape[0], schedule.shape[1]),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=_params(interpret, "parallel", "arbitrary"),
        interpret=interpret)(*schedule, *operands)


def _layout(q, k, v):
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    return (q.reshape(b * hkv, hq // hkv, s, d), k.reshape(b * hkv, s, d),
            v.reshape(b * hkv, s, d))


def _static(cfg):
    """The kernels' keyword arguments from ``cfg = (length, block, scale,
    block_q, block_k, interpret)``."""
    length, block, scale, block_q, block_k, _ = cfg
    return dict(sm_scale=scale, rule=functools.partial(
        _rule_tile, block_q=block_q, block_k=block_k, length=length,
        block=block))


def _forward(q, k, v, cfg):
    length, block, _, block_q, block_k, interpret = cfg
    b, hq, s, d = q.shape
    qf, kf, vf = _layout(q, k, v)
    bh, group = qf.shape[:2]
    _log_path("block_attention_fwd", f"{block_q}x{block_k}")
    qspec, kspec, stat = _specs(group, block_q, block_k, d)
    out, lse = _call(
        functools.partial(_fwd_kernel, group=group, **_static(cfg)),
        "block_attn_fwd", tile_schedule(length, block, block_q, block_k),
        [qspec, kspec, kspec], (qspec, stat),
        (jax.ShapeDtypeStruct(qf.shape, q.dtype),
         jax.ShapeDtypeStruct((bh, group, s, 1), jnp.float32)),
        [pltpu.VMEM((group, block_q, d), jnp.float32),
         pltpu.VMEM((group, block_q, 1), jnp.float32),
         pltpu.VMEM((group, block_q, 1), jnp.float32)],
        interpret, (qf, kf, vf))
    return out.reshape(b, hq, s, d), lse.reshape(b, hq, s)


def _backward(q, k, v, out, lse, g, cfg):
    """dQ, dK, dV: one kernel that visits each tile once where a KV head's
    dK and dV fit ``ops/sparse_attention.py``'s budget for them, the dq and
    dkv kernels past it."""
    s, d = q.shape[2:]
    qf, kf, vf = _layout(q, k, v)
    bh, group = qf.shape[:2]
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, group, s, 1)
    operands = (qf, kf, vf, g.astype(q.dtype).reshape(qf.shape),
                lse.reshape(bh, group, s, 1), delta)
    fused = _bwd_is_fused(s, d, k.dtype)
    _log_path("block_attention_bwd", "fused" if fused else "split")
    dq, dk, dv = (_bwd_fused if fused else _bwd_split)(operands, cfg)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _bwd_fused(operands, cfg):
    length, block, _, block_q, block_k, interpret = cfg
    qf, kf, vf = operands[:3]
    group, s, d = qf.shape[1:]
    qspec, kspec, stat = _specs(group, block_q, block_k, d)
    row = _row_spec(s, d)
    return _call(
        functools.partial(_bwd_dqkv_kernel, **_static(cfg)),
        "block_attn_bwd_dqkv", tile_schedule(length, block, block_q, block_k),
        [qspec, kspec, kspec, qspec, stat, stat], (qspec, row, row),
        (jax.ShapeDtypeStruct(qf.shape, qf.dtype),
         jax.ShapeDtypeStruct(kf.shape, kf.dtype),
         jax.ShapeDtypeStruct(vf.shape, vf.dtype)),
        [pltpu.VMEM((group, block_q, d), jnp.float32),
         pltpu.VMEM((s, d), jnp.float32), pltpu.VMEM((s, d), jnp.float32)],
        interpret, operands)


def _bwd_split(operands, cfg):
    length, block, _, block_q, block_k, interpret = cfg
    qf, kf, vf = operands[:3]
    group, _, d = qf.shape[1:]
    static = _static(cfg)
    qspec, kspec, stat = _specs(group, block_q, block_k, d)
    ins = [qspec, kspec, kspec, qspec, stat, stat]
    dq = _call(
        functools.partial(_bwd_dq_kernel, **static), "block_attn_bwd_dq",
        tile_schedule(length, block, block_q, block_k), ins, qspec,
        jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        [pltpu.VMEM((group, block_q, d), jnp.float32)], interpret, operands)
    dk, dv = _call(
        functools.partial(_bwd_dkv_kernel, **static), "block_attn_bwd_dkv",
        tile_schedule(length, block, block_q, block_k, "kq"), ins,
        (kspec, kspec),
        (jax.ShapeDtypeStruct(kf.shape, kf.dtype),
         jax.ShapeDtypeStruct(vf.shape, vf.dtype)),
        [pltpu.VMEM((block_k, d), jnp.float32),
         pltpu.VMEM((block_k, d), jnp.float32)], interpret, operands)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attend(q, k, v, fwd_cfg, bwd_cfg):
    """The forward kernel's ``cfg`` and the backward's: they differ in the
    key tile."""
    return _forward(q, k, v, fwd_cfg)


def _attend_fwd(q, k, v, fwd_cfg, bwd_cfg):
    out, lse = _forward(q, k, v, fwd_cfg)
    # the names sit on the values the backward kernels read
    out, lse = checkpoint_name(out, ATTN_OUT), checkpoint_name(lse, ATTN_LSE)
    return (out, lse), (q, k, v, out, lse)


def _attend_bwd(fwd_cfg, bwd_cfg, res, g):
    return _backward(*res, g[0], bwd_cfg)


_attend.defvjp(_attend_fwd, _attend_bwd)


def block_attention(q, k, v, length: int, block: int,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Softmax attention of ``q [B, Hq, 2 length, D]`` over the keys the
    module's rule lets each query see, ``k, v [B, Hkv, 2 length, D]`` shared
    by ``Hq / Hkv`` query heads each; ``length`` and ``block`` (a power of two)
    are static.
    Returns ``(out, lse [B, Hq, 2 length])``; the logsumexp carries no
    gradient. ``block_q`` and ``block_k`` are every kernel's tiles (each has
    to divide ``length``; by default the largest of 512, 256, 128 that does,
    else ``length``, and for the forward's keys of 1 024 too). The forward of
    the ``custom_vjp`` names ``out`` and ``lse`` :data:`ATTN_OUT` and
    :data:`ATTN_LSE` for a checkpoint to keep."""
    if q.shape[2] != 2 * length or length % block or block & (block - 1):
        raise ValueError(f"a row of {q.shape[2]} positions is not a clean and "
                         f"a noised copy of {length} tokens in blocks of "
                         f"{block} (a power of two: the kernels take a "
                         f"position's block by a shift)")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fwd_cfg, bwd_cfg = ((int(length), int(block), float(scale), *tile,
                         bool(interpret))
                        for tile in _tiles(length, block_q, block_k))
    return _attend(q, k, v, fwd_cfg, bwd_cfg)
