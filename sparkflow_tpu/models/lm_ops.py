"""What the decoder families (``sparse_moe_lm.py``, ``block_diffusion_lm.py``,
``looped_lm.py``) compute alike, written once: RMSNorm, rotary positions, a
bias-free projection, the head's float32 logits and a row's weighted
cross-entropy a stretch at a time."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                     keepdims=True) + eps) * scale
    return y.astype(x.dtype)


def rope(x, theta: float, positions=None):
    """Rotary positions over the whole last axis of ``x [B, S, ..., D]``
    (rotate-half); the position of index ``i`` along axis 1 is
    ``positions[i]``, ``i`` itself by default; computed in float32."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (d // 2,))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return (x32 * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
            ).astype(x.dtype)


def dense(x, kernel):
    return jnp.matmul(x, kernel.astype(x.dtype))


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
