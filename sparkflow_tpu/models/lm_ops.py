"""What the decoder families (``sparse_moe_lm.py``, ``block_diffusion_lm.py``,
``looped_lm.py``) compute alike, written once: RMSNorm, rotary positions, a
bias-free projection, ``q`` and ``k`` on their way from it to the attention
kernels, the head's float32 logits and a row's weighted cross-entropy a
stretch at a time.

:func:`heads` makes a block's ``q`` and ``k`` (per-head RMSNorm where the
family has one, rotary positions, the head-major layout: one kernel,
``ops/head_rotary.py``). :func:`rms_norm` and :func:`rope` are the same
arithmetic in ``jnp``: they remain for the norms over the hidden width, for
keye's indexer (heads of 64 that are not transposed) and as what the tests
hold :func:`heads` to."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.head_rotary import head_rotary


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                     keepdims=True) + eps) * scale
    return y.astype(x.dtype)


def _angles(s: int, d: int, theta: float, positions):
    """The rotation angles of a head of ``d`` at each of ``s`` indices,
    float32 ``[s, d // 2]``; index ``i`` is at ``positions[i]``, ``i`` itself
    by default."""
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.float32)
    return positions.astype(jnp.float32)[:, None] * inv[None, :]


def rope(x, theta: float, positions=None):
    """Rotary positions over the whole last axis of ``x [B, S, ..., D]``
    (rotate-half); the position of index ``i`` along axis 1 is
    ``positions[i]``, ``i`` itself by default; computed in float32."""
    s, d = x.shape[1], x.shape[-1]
    ang = _angles(s, d, theta, positions).reshape(
        (1, s) + (1,) * (x.ndim - 3) + (d // 2,))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return (x32 * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
            ).astype(x.dtype)


def dense(x, kernel):
    return jnp.matmul(x, kernel.astype(x.dtype))


def heads(x, n: int, scale, eps: float, theta: float, positions=None):
    """A projection's output ``x [B, S, n * D]`` as the attention kernels
    read it, ``[B, n, S, D]``: ``transpose(rope(rms_norm(x.reshape(B, S, n,
    D), scale, eps), theta, positions), (0, 2, 1, 3))`` in one pass over
    ``x`` (:func:`~sparkflow_tpu.ops.head_rotary.head_rotary`; ``scale``
    ``None``: no norm). The positions' cosines and sines are two float32 ``[S,
    D]`` tables made here, the rotate-half's sign in the sines."""
    ang = _angles(x.shape[1], x.shape[-1] // n, theta, positions)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return head_rotary(x, n, jnp.concatenate([cos, cos], axis=-1),
                       jnp.concatenate([-sin, sin], axis=-1), scale, eps)


def head_logits(h, kernel):
    """The head on normed states ``h [..., hidden]``: float32 logits."""
    return jnp.matmul(h, kernel.astype(h.dtype),
                      preferred_element_type=jnp.float32)


def weighted_nll(head, x, tgt, weight, head_block: int):
    """``sum_i weight[i] * cross-entropy(head(x[i]), tgt[i])`` of each row:
    ``x [..., S, h]``, ``tgt [..., S]`` columns of the head, ``weight [...,
    S]`` or, for several sums over the same logits, ``[..., S, K]`` -> ``[...]``
    or ``[..., K]``. ``head`` makes float32 logits from a stretch of ``x``.
    They are made and reduced ``head_block`` positions of a row at a time
    (and again in the backward pass): a whole row's are ``S x vocab`` floats,
    three times over. ``weight`` is an argument of the stretch, so it has a
    gradient: each position's cross-entropy."""
    rows, s = tgt.shape[:-1], tgt.shape[-1]
    c = head_block if s % head_block == 0 else s

    @jax.checkpoint
    def stretch(a):
        xs, t, w = a
        logits = head(xs)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        return jnp.sum(nll.reshape((c,) + (1,) * (w.ndim - 1)) * w, axis=0)

    split = lambda a, tail: a.reshape((-1, c) + tail)
    sums = jax.lax.map(stretch, (split(x, x.shape[-1:]), split(tgt, ()),
                                 split(weight, weight.shape[tgt.ndim:])))
    return jnp.sum(sums.reshape(rows + (s // c,) + sums.shape[1:]),
                   axis=len(rows))
