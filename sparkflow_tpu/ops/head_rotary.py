"""A projection's output on its way to the attention kernels, in one pass.

The decoder families' blocks make ``q`` and ``k`` as ``[B, S, H * D]`` (a
matrix product's output) and their attention kernels read ``[B, H, S, D]``.
In between lie an RMSNorm over each head (the MoE families), the rotary
positions (rotate-half over the whole head) and the change of layout. Written
in ``jnp`` that is four to six passes over the array, two of them over float32
copies of it. :func:`head_rotary` reads the array once and writes it once:
pallas kernels ``head_rotary_fwd`` and, for the gradient, ``head_rotary_bwd``.

A grid step holds ``block_s`` positions of ``group`` heads: the input block
is ``[block_s, group * D]`` of the product's rows (long contiguous runs), the
output ``group`` blocks of ``[block_s, D]``, so the layout change is the block
specs'. Inside, a head at a time, in float32: ``y = x * rsqrt(mean(x^2) + eps)
* scale`` where there is a norm, then ``y * cos + roll(y, D / 2) * sin`` with
the rotate-half's sign folded into the ``sin`` table (``-sin`` on the first
half of a head), so the half-swap is one lane rotation and nothing is
concatenated. The transpose of the rotation is the rotation by the negative
angle and needs no residual; the norm's transpose reads the product's output
again, the only residual, and returns the scale's gradient as float32 partial
sums, one a grid step.

On a CPU the kernels run interpreted, at any head width. On a TPU a head is
whole lane tiles (``D % 128 == 0``) or the call raises: there is no ``jnp``
path (``models/lm_ops.rope`` and ``rms_norm`` are the reference the tests
hold this file to). ``record_attention_paths()`` holds ``head_rotary:pallas``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _log_path
from .sparse_attention import _block

# a grid step's block of the product's output: up to ``_WIDTH`` of its
# columns (whole heads; 2 KB runs in bfloat16) by ``_block(S)`` positions
_WIDTH = 1024


def _group(h: int, d: int) -> int:
    """Heads a grid step: the most that divide ``h`` within ``_WIDTH``."""
    return max(g for g in range(1, h + 1)
               if h % g == 0 and (g == 1 or g * d <= _WIDTH))


def _rotate(y, cos, sin):
    """Rotary positions on one head ``y [block_s, D]``, float32; ``sin``
    carries the rotate-half's sign."""
    return y * cos + pltpu.roll(y, y.shape[-1] // 2, 1) * sin


def _fwd_kernel(x_ref, cos_ref, sin_ref, *refs, group, d, eps, norm):
    o_ref = refs[-1]
    cos, sin = cos_ref[...], sin_ref[...]
    for j in range(group):
        y = x_ref[0, :, j * d:(j + 1) * d].astype(jnp.float32)
        if norm:
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                                  + eps) * refs[0][...]
        o_ref[0, j] = _rotate(y, cos, sin).astype(o_ref.dtype)


def _bwd_kernel(g_ref, cos_ref, sin_ref, *refs, group, d, eps, norm):
    if norm:
        x_ref, scale_ref, dx_ref, ds_ref = refs
        ds = jnp.zeros((1, d), jnp.float32)
    else:
        dx_ref, = refs
    # the rotation's transpose: by the negative angle
    cos, sin = cos_ref[...], -sin_ref[...]
    for j in range(group):
        dy = _rotate(g_ref[0, j].astype(jnp.float32), cos, sin)
        if norm:
            x = x_ref[0, :, j * d:(j + 1) * d].astype(jnp.float32)
            r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            xh = x * r
            ds += jnp.sum(dy * xh, axis=0, keepdims=True)
            gy = dy * scale_ref[...]
            dy = r * (gy - xh * jnp.mean(gy * xh, axis=-1, keepdims=True))
        dx_ref[0, :, j * d:(j + 1) * d] = dy.astype(dx_ref.dtype)
    if norm:
        ds_ref[0, 0, 0] = ds


def _specs(group, d, block_s):
    rows = pl.BlockSpec((1, block_s, group * d), lambda b, si, g: (b, si, g))
    heads = pl.BlockSpec((1, group, block_s, d),
                         lambda b, si, g: (b, g, si, 0))
    table = pl.BlockSpec((block_s, d), lambda b, si, g: (si, 0))
    scale = pl.BlockSpec((1, d), lambda b, si, g: (0, 0))
    return rows, heads, table, scale


def _params(interpret):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 3)


def _forward(x, scale, cos, sin, cfg):
    h, eps, block_s, group, interpret = cfg
    b, s, hd = x.shape
    d = hd // h
    rows, heads, table, sspec = _specs(group, d, block_s)
    norm = () if scale is None else (scale.reshape(1, d).astype(jnp.float32),)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, group=group, d=d, eps=eps,
                          norm=scale is not None),
        name="head_rotary_fwd",
        grid=(b, s // block_s, h // group),
        in_specs=[rows, table, table] + [sspec] * len(norm),
        out_specs=heads,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), x.dtype),
        compiler_params=_params(interpret),
        interpret=interpret,
    )(x, cos, sin, *norm)


def _backward(g, x, scale, cos, sin, cfg):
    h, eps, block_s, group, interpret = cfg
    b, _, s, d = g.shape
    rows, heads, table, sspec = _specs(group, d, block_s)
    grid = (b, s // block_s, h // group)
    dx = jax.ShapeDtypeStruct((b, s, h * d), g.dtype)
    call = functools.partial(
        pl.pallas_call,
        functools.partial(_bwd_kernel, group=group, d=d, eps=eps,
                          norm=scale is not None),
        name="head_rotary_bwd", grid=grid,
        compiler_params=_params(interpret), interpret=interpret)
    if scale is None:
        return call(in_specs=[heads, table, table], out_specs=rows,
                    out_shape=dx)(g, cos, sin), None
    sums = pl.BlockSpec((1, 1, 1, 1, d), lambda b, si, g: (b, si, g, 0, 0))
    dx, ds = call(
        in_specs=[heads, table, table, rows, sspec], out_specs=(rows, sums),
        out_shape=(dx, jax.ShapeDtypeStruct(grid + (1, d), jnp.float32)),
    )(g, cos, sin, x, scale.reshape(1, d).astype(jnp.float32))
    return dx, jnp.sum(ds, axis=(0, 1, 2, 3)).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _head_rotary(x, scale, cos, sin, cfg):
    return _forward(x, scale, cos, sin, cfg)


def _head_rotary_fwd(x, scale, cos, sin, cfg):
    # the norm's transpose reads the product's output again; the rotation's
    # reads nothing of the forward
    return (_forward(x, scale, cos, sin, cfg),
            (None if scale is None else x, scale, cos, sin))


def _head_rotary_bwd(cfg, res, g):
    x, scale, cos, sin = res
    return _backward(g, x, scale, cos, sin, cfg) + (None, None)


_head_rotary.defvjp(_head_rotary_fwd, _head_rotary_bwd)


def head_rotary(x, num_heads: int, cos, sin, scale=None, eps: float = 0.0):
    """``x [B, S, H * D]`` (``H = num_heads``) -> ``[B, H, S, D]`` in ``x``'s
    type: RMSNorm over each head with ``scale [D]`` and ``eps`` (``scale``
    ``None``: no norm), then the rotation of position ``i``'s head by the
    angles whose cosines are ``cos[i]`` and whose sines, negated on the first
    half of a head, are ``sin[i]`` (both float32 ``[S, D]``), all in float32.
    Differentiable in ``x`` and ``scale``."""
    b, s, hd = x.shape
    d = hd // num_heads
    interpret = jax.default_backend() != "tpu"
    if hd % num_heads or d % 2 or not interpret and d % 128:
        raise ValueError(
            f"head_rotary: {hd} columns are not {num_heads} heads of whole "
            f"lane tiles (an even width; on a TPU a multiple of 128, where "
            f"the layout change is the block specs' and the half-swap one "
            f"lane rotation): got heads of {hd / num_heads:g}")
    _log_path("head_rotary", "pallas")
    return _head_rotary(x, scale, cos, sin,
                        (num_heads, float(eps), _block(s),
                         _group(num_heads, d), interpret))
