"""Dropless experts under static shapes: top-k routing over all the experts,
the rows routed to the experts held here grouped by expert, and a grouped
matrix product over those groups.

An expert layer that holds ``held`` of the router's experts (one chip's
share under expert parallelism; all of them on one chip) computes its own
experts' part of the layer's output and drops no token:

- :func:`route_top_k`: the router's softmax over **all** experts in float32,
  each token's top ``k`` and their gates.
- :func:`group_rows`: every (token, choice) pair whose expert is held here
  gets a row of a buffer in which each expert's rows are contiguous and
  start at a multiple of ``tile``. The buffer has the worst case's size
  (every token on ``min(k, held)`` held experts), so its shape is static;
  how many of its tiles are in use is a value, and the kernels skip the rest.
- :func:`grouped_matmul`: ``rows [R, K] x w [held, K, N]``, each tile of
  rows against its own expert's matrix (pallas kernels ``expert_gmm`` and,
  for the weights' gradient, ``expert_tgmm``), with
  :func:`grouped_matmul_reference` beside it.
- :func:`dispatch` / :func:`combine`: tokens to rows and rows back to
  tokens, gate-weighted. Both directions of both are row copies by the
  pallas kernels ``expert_rows_in`` (rows from tokens) and
  ``expert_rows_out`` (tokens from rows, summed in float32), which visit
  the tiles in use and nothing else of the buffer: what they cost follows
  ``tiles_used`` and the indices, not :func:`rows_bound`. :func:`_take`, an
  XLA gather at the index's static shape, is their plain form. Off a TPU
  the same kernels run interpreted.
- :func:`dropless_experts`: the layer (SiLU-gated experts) from these parts;
  :func:`rows_live` counts the rows it visits.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 256
_VMEM_LIMIT = 64 * 1024 * 1024


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def route_top_k(router_logits, k: int, normalize: bool = True):
    """``router_logits [N, E]`` (float32) -> ``(probs [N, E], gates [N, k],
    experts [N, k])``: softmax over all experts, each token's top ``k``,
    their probabilities normalised to one when ``normalize``."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if normalize:
        top = top / jnp.maximum(jnp.sum(top, axis=-1, keepdims=True), 1e-20)
    return probs, top, idx.astype(jnp.int32)


def balance_loss(probs, experts):
    """The load-balancing loss over all ``E`` experts of one group of tokens
    (``probs [N, E]``, ``experts [N, k]``): ``E * sum_e f_e p_e``, ``f_e``
    the choices that fell on expert ``e`` over the tokens, ``p_e`` its mean
    probability."""
    e = probs.shape[-1]
    chosen = jnp.sum(jax.nn.one_hot(experts, e, dtype=jnp.float32), axis=-2)
    return e * jnp.sum(jnp.mean(chosen, axis=0) * jnp.mean(probs, axis=0))


class Rows(NamedTuple):
    """Where the pairs routed here lie (:func:`group_rows`)."""
    row_of_pair: jax.Array      # [N, k] int32; ``rows`` for a pair not here
    token_of_row: jax.Array     # [rows] int32; ``N`` for a padding row
    tile_expert: jax.Array      # [rows // tile] int32, local expert of a tile
    tiles_used: jax.Array       # [1] int32
    load: jax.Array             # [held] int32: pairs each held expert got


def rows_bound(tokens: int, k: int, held: int, tile: int = TILE) -> int:
    """Rows of the buffer: the worst case, in whole tiles."""
    return (tokens * min(k, held) // tile + held) * tile


def _tiles(load, tile: int):
    """Tiles of each held expert: its pairs in whole tiles, at least one."""
    return jnp.maximum(1, (load + tile - 1) // tile)


def rows_live(load, tile: int = TILE):
    """Rows of the tiles in use (``tiles_used * tile``) for the loads
    ``[..., held]`` :func:`group_rows` counted: what the layer's kernels and
    row copies visit, of :func:`rows_bound`."""
    return jnp.sum(_tiles(load, tile), axis=-1) * tile


def group_rows(experts, first: int, held: int, tile: int = TILE) -> Rows:
    """Lay out the pairs of ``experts [N, k]`` that fall on the experts
    ``first .. first + held`` by expert, each expert's rows from a tile's
    start (at least one tile an expert, so that every expert's gradient is
    written)."""
    n, k = experts.shape
    rows = rows_bound(n, k, held, tile)
    local = experts - first
    here = (local >= 0) & (local < held)
    flat = jnp.where(here, local, held).reshape(-1)
    onehot = (flat[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :]
              ).astype(jnp.int32)                                 # [N k, held]
    load = jnp.sum(onehot, axis=0)
    before = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
    tiles = _tiles(load, tile)
    tile_end = jnp.cumsum(tiles)
    start = (tile_end - tiles) * tile
    flat_here = here.reshape(-1)
    row = jnp.where(flat_here,
                    start[jnp.minimum(flat, held - 1)] + before, rows)
    token = jnp.arange(n * k, dtype=jnp.int32) // k
    token_of_row = jnp.full((rows + 1,), n, jnp.int32).at[row].set(token)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(rows // tile, dtype=jnp.int32),
                         side="right"), held - 1).astype(jnp.int32)
    return Rows(row.reshape(n, k).astype(jnp.int32), token_of_row[:rows],
                tile_expert, tile_end[-1:].astype(jnp.int32), load)


# ---------------------------------------------------------------------------
# tokens -> rows -> tokens: row copies over the tiles in use
# ---------------------------------------------------------------------------


def _used(i, used):
    """A tile past the last one in use takes the last one's blocks: nothing
    new is fetched for it, and no output block changes hands."""
    return jnp.minimum(i, used[0] - 1)


def _take(a, index):
    """``a[index]`` with zeros where ``index`` is ``len(a)`` (a padding row,
    a pair not here): a clamped gather and a mask, no padded copy of ``a``.
    The plain form of the movements below, which the tests hold the kernels
    to; its cost is the index's static shape, whatever the rows in use."""
    n = a.shape[0]
    picked = a[jnp.minimum(index, n - 1)]
    return jnp.where((index < n)[..., None], picked, 0).astype(a.dtype)


_SPARE = 8                              # a sum's rows past the tokens'
_STEP = 4                               # tiles to a step of the kernels' grid
_RESIDENT = 32 * 1024 * 1024            # bytes of a token-side block in VMEM


def _parts(dtype) -> int:
    """Values of ``dtype`` a 32-bit word carries."""
    if dtype not in (jnp.float32, jnp.bfloat16):
        raise TypeError(f"the experts' rows move as float32 or bfloat16, "
                        f"not {dtype}")
    return 4 // jnp.dtype(dtype).itemsize


def _column_blocks(tokens: int, words: int) -> int:
    """Column blocks of a token-side array of ``words`` 32-bit columns such
    that one block of all the tokens stays in VMEM."""
    blocks = 1
    while (tokens * (words // blocks) * 4 > _RESIDENT
           and (words // blocks) % 256 == 0):
        blocks *= 2
    return blocks


def _words(a, blocks: int):
    """``a [N, h]`` as 32-bit words ``[N, W]``, so that a row is whole
    sublanes of a VMEM block whatever its type (a bfloat16 row alone is half
    of every word of a packed tile and cannot be addressed). Within each of
    the ``blocks`` column blocks a word holds column ``j`` low and column
    ``j + half`` high: the halves come apart again by a shift and a mask
    (:func:`_values`)."""
    bits = lambda v: jax.lax.bitcast_convert_type(v.astype(jnp.float32),
                                                  jnp.uint32)
    if _parts(a.dtype) == 1:
        return bits(a)
    half = a.shape[1] // (2 * blocks)
    return jnp.concatenate(
        [(bits(a[:, 2 * b * half:(2 * b + 1) * half]) >> 16)
         | (bits(a[:, (2 * b + 1) * half:(2 * b + 2) * half])
            & jnp.uint32(0xFFFF0000)) for b in range(blocks)], axis=1)


def _values(w, parts: int):
    """The float32 values of a block of words, one array a part."""
    as_f32 = lambda u: jax.lax.bitcast_convert_type(u, jnp.float32)
    if parts == 1:
        return [as_f32(w)]
    return [as_f32(w << 16), as_f32(w & jnp.uint32(0xFFFF0000))]


def _each_row(tile: int, move):
    """``move(r)`` for the rows of a tile in turn, eight to an iteration of
    the loop so that one row's address arithmetic runs beside another's
    loads and stores (a tile is whole sublanes, a multiple of eight)."""
    def eight(g, carry):
        for u in range(8):
            move(g * 8 + u)
        return carry

    jax.lax.fori_loop(0, tile // 8, eight, 0)


def _rows_in_kernel(used_ref, idx_ref, words_ref, *refs, tokens: int,
                    tile: int, parts: int, scaled: bool, dotted: bool):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    with_ref = refs.pop(0) if dotted else None
    o_ref = refs.pop(0)
    dots_ref = refs.pop(0) if dotted else None
    got_ref, = refs

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        def copy(r):
            t = idx_ref[0, 0, r]
            row = words_ref[pl.ds(jnp.minimum(t, tokens - 1), 1), :]
            got_ref[pl.ds(r, 1), :] = jnp.where(t < tokens, row, 0)

        _each_row(tile, copy)
        values = _values(got_ref[...], parts)
        width = values[0].shape[1]
        if dotted:
            dots_ref[...] = sum(
                jnp.sum(v * with_ref[:, k * width:(k + 1) * width].astype(
                    jnp.float32), axis=-1, keepdims=True)
                for k, v in enumerate(values))
        for k, v in enumerate(values):
            if scaled:
                v = v * scale_ref[...]
            o_ref[:, k * width:(k + 1) * width] = v.astype(o_ref.dtype)


def _steps(rows: int, tile: int, tiles_used):
    """The rows a step of the kernels' grid takes (``_STEP`` tiles if the
    buffer is whole steps: a step past the last one in use still costs its
    turn, a third of a microsecond) and the steps in use ``[1]``."""
    tiles = next(g for g in (_STEP, 2, 1) if rows // tile % g == 0)
    return tile * tiles, (tiles_used + tiles - 1) // tiles


def _index_spec(step: int):
    """A step's block of the rows' index ``[steps, 1, step]``, in scalar
    memory."""
    return pl.BlockSpec((1, 1, step), lambda b, i, used: (_used(i, used), 0, 0),
                        memory_space=pltpu.SMEM)


def _scale_spec(step: int):
    """A step's block of the rows' scale ``[rows, 1]``."""
    return pl.BlockSpec((step, 1), lambda b, i, used: (_used(i, used), 0))


_ROW_KERNELS = dict(dimension_semantics=("arbitrary", "arbitrary"),
                    vmem_limit_bytes=100 * 1024 * 1024)  # of 128 MiB


def _rows_in(a, token_of_row, tiles_used, tile, interpret, scale=None,
             dot_with=None):
    """Rows from tokens (pallas kernel ``expert_rows_in``): row ``r`` of the
    result is ``a[token_of_row[r]]``, times ``scale[r]`` if given, zeros
    where the index is ``len(a)``; only the steps that hold a tile in use
    are visited and written. With ``dot_with [rows, h]`` also each row's product with the
    unscaled ``a[token_of_row[r]]``, summed over the width in float32
    ``[rows, 1]``. The tokens' side stays in VMEM (as 32-bit words, a column
    block at a time) and a row moves as sublane-strided loads and stores."""
    n, h = a.shape
    rows = token_of_row.shape[0]
    parts = _parts(a.dtype)
    if h % parts:
        raise ValueError(f"a width of {h} is no whole number of 32-bit words")
    blocks = _column_blocks(n, h // parts)
    hw, ww = h // blocks, h // parts // blocks
    step, steps_used = _steps(rows, tile, tiles_used)
    at = lambda b, i, used: (_used(i, used), b)
    in_specs = [_index_spec(step),
                pl.BlockSpec((n, ww), lambda b, i, used: (0, b),
                             pipeline_mode=pl.Buffered(1))]
    operands = [token_of_row.reshape(-1, 1, step), _words(a, blocks)]
    out_specs = [pl.BlockSpec((step, hw), at)]
    out_shape = [jax.ShapeDtypeStruct((rows, h), a.dtype)]
    if scale is not None:
        in_specs.append(_scale_spec(step))
        operands.append(scale)
    if dot_with is not None:
        in_specs.append(pl.BlockSpec((step, hw), at))
        operands.append(dot_with)
        out_specs.append(pl.BlockSpec(
            (None, step, 1), lambda b, i, used: (b, _used(i, used), 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((blocks, rows, 1), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_rows_in_kernel, tokens=n, tile=step, parts=parts,
                          scaled=scale is not None,
                          dotted=dot_with is not None),
        name="expert_rows_in",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks, rows // step),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((step, ww), jnp.uint32)]),
        out_shape=out_shape,
        # the rows take the place of the buffer they were multiplied with:
        # a tile of it is read before the same tile is written, and its
        # caller (the gates' gradient) needs it no longer
        input_output_aliases=({len(operands): 0} if dot_with is not None
                              else {}),
        compiler_params=(None if interpret
                         else pltpu.CompilerParams(**_ROW_KERNELS)),
        interpret=interpret,
    )(steps_used, *operands)
    if dot_with is None:
        return out[0], None
    return out[0], (out[1][0] if blocks == 1 else jnp.sum(out[1], axis=0))


def _rows_out_kernel(used_ref, idx_ref, src_ref, *refs, tokens: int,
                     tile: int, scaled: bool):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    o_ref, acc_ref, row_ref = refs
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < used_ref[0])
    def _():
        v = src_ref[...].astype(jnp.float32)
        row_ref[...] = v * scale_ref[...] if scaled else v

        def add(r):
            t = jnp.minimum(idx_ref[0, 0, r], tokens)
            acc_ref[pl.ds(t, 1), :] += row_ref[pl.ds(r, 1), :]

        _each_row(tile, add)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[pl.ds(0, tokens), :].astype(o_ref.dtype)


def _rows_out(a, token_of_row, tiles_used, tokens, tile, interpret,
              scale=None):
    """Tokens from rows (pallas kernel ``expert_rows_out``): ``y[n] = sum
    over the rows r of token n of scale[r] * a[r]`` (``scale`` ones if not
    given), over the tiles in use alone. A token's sum is kept in float32
    in VMEM (a column block of all the tokens at a time), a row adds into it
    as one sublane-strided load, add and store, and the sums are rounded
    once, when the block is written. A padding row adds into a spare row
    that is never written out."""
    rows, h = a.shape
    _parts(a.dtype)
    blocks = _column_blocks(tokens, h)
    hw = h // blocks
    step, steps_used = _steps(rows, tile, tiles_used)
    in_specs = [_index_spec(step),
                pl.BlockSpec((step, hw),
                             lambda b, i, used: (_used(i, used), b))]
    operands = [token_of_row.reshape(-1, 1, step), a]
    if scale is not None:
        in_specs.append(_scale_spec(step))
        operands.append(scale)
    return pl.pallas_call(
        functools.partial(_rows_out_kernel, tokens=tokens, tile=step,
                          scaled=scale is not None),
        name="expert_rows_out",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks, rows // step),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tokens, hw), lambda b, i, used: (0, b)),
            scratch_shapes=[pltpu.VMEM((tokens + _SPARE, hw), jnp.float32),
                            pltpu.VMEM((step, hw), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, h), a.dtype),
        compiler_params=(None if interpret
                         else pltpu.CompilerParams(**_ROW_KERNELS)),
        interpret=interpret,
    )(steps_used, *operands)


def _gate_of_row(gates, row_of_pair, rows: int):
    """Each row's gate ``[rows, 1]`` in float32, zero on a padding row."""
    return jnp.zeros((rows + 1,), jnp.float32).at[row_of_pair.reshape(-1)].set(
        gates.reshape(-1).astype(jnp.float32))[:rows, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _dispatch(x, token_of_row, row_of_pair, tiles_used, tile, interpret):
    with jax.named_scope("expert_rows"):
        return _rows_in(x, token_of_row, tiles_used, tile, interpret)[0]


def _dispatch_fwd(x, token_of_row, row_of_pair, tiles_used, tile, interpret):
    return (_dispatch(x, token_of_row, row_of_pair, tiles_used, tile,
                      interpret), (token_of_row, row_of_pair, tiles_used))


def _dispatch_bwd(tile, interpret, res, g):
    token_of_row, row_of_pair, tiles_used = res
    with jax.named_scope("expert_rows"):
        dx = _rows_out(g, token_of_row, tiles_used, row_of_pair.shape[0],
                       tile, interpret)
    return dx, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def dispatch(x, token_of_row, row_of_pair, tiles_used, tile: int = TILE,
             interpret: Optional[bool] = None):
    """``x [N, h]`` -> the rows' inputs ``[rows, h]``: zeros on the padding
    rows of a tile in use; the rows of the tiles past ``tiles_used`` hold
    zeros or whatever was there (the kernel writes whole steps of its grid,
    :func:`_steps`). Differentiable in ``x``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _dispatch(x, token_of_row, row_of_pair, tiles_used, tile,
                     interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _combine(out, gates, token_of_row, row_of_pair, tiles_used, tile,
             interpret):
    return _combine_fwd(out, gates, token_of_row, row_of_pair, tiles_used,
                        tile, interpret)[0]


def _combine_fwd(out, gates, token_of_row, row_of_pair, tiles_used, tile,
                 interpret):
    with jax.named_scope("expert_rows"):
        gate_of_row = _gate_of_row(gates, row_of_pair, out.shape[0])
        y = _rows_out(out, token_of_row, tiles_used, gates.shape[0], tile,
                      interpret, scale=gate_of_row)
    # the rows' gates are a scatter of every pair: made once, kept
    return y, (out, gates, gate_of_row, token_of_row, row_of_pair, tiles_used)


def _combine_bwd(tile, interpret, res, g):
    out, gates, gate_of_row, token_of_row, row_of_pair, tiles_used = res
    with jax.named_scope("expert_rows"):
        d_out, dots = _rows_in(g, token_of_row, tiles_used, tile, interpret,
                               scale=gate_of_row, dot_with=out)
        d_gates = _take(dots.reshape(-1, 1), row_of_pair.reshape(-1))
    return (d_out, d_gates.reshape(gates.shape).astype(gates.dtype), None,
            None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def combine(out, gates, token_of_row, row_of_pair, tiles_used,
            tile: int = TILE, interpret: Optional[bool] = None):
    """The rows' outputs ``[rows, h]`` back to tokens: ``y[n] = sum_j
    gates[n, j] * out[row_of_pair[n, j]]``, a pair not here adding nothing,
    summed in float32 and rounded once. Reads the tiles in use alone.
    Differentiable in ``out`` and ``gates``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _combine(out, gates, token_of_row, row_of_pair, tiles_used, tile,
                    interpret)


# ---------------------------------------------------------------------------
# the grouped product
# ---------------------------------------------------------------------------


def grouped_matmul_reference(x, w, tile_expert, tiles_used, tile: int = TILE,
                             transpose_w: bool = False):
    """Plain ``jnp``: every row against the matrix of its tile's expert;
    rows of unused tiles give zeros."""
    rows = x.shape[0]
    expert_of_row = jnp.repeat(tile_expert, tile, total_repeat_length=rows)
    used = (jnp.arange(rows) // tile) < tiles_used[0]
    wr = w[expert_of_row]
    spec = "rk,rnk->rn" if transpose_w else "rk,rkn->rn"
    out = jnp.einsum(spec, x, wr, preferred_element_type=jnp.float32)
    return jnp.where(used[:, None], out, 0.0).astype(x.dtype)


def _gmm_kernel(te_ref, used_ref, x_ref, w_ref, o_ref, *, transpose_w: bool):
    @pl.when(pl.program_id(0) < used_ref[0])
    def _():
        dims = (((1,), (1,)), ((), ())) if transpose_w else (
            ((1,), (0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _gmm(x, w, tile_expert, tiles_used, tile, transpose_w, interpret):
    rows, kdim = x.shape
    held, wk, wn = w.shape
    n = wk if transpose_w else wn
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w),
        name="expert_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // tile,),
            in_specs=[
                pl.BlockSpec((tile, kdim),
                             lambda i, te, used: (_used(i, used), 0)),
                pl.BlockSpec((1, wk, wn),
                             lambda i, te, used: (te[_used(i, used)], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tile, n),
                                   lambda i, te, used: (_used(i, used), 0))),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert, tiles_used, x, w)


def _tgmm_kernel(te_ref, used_ref, x_ref, g_ref, o_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i < used_ref[0])
    def _():
        first = jnp.logical_or(
            i == 0, te_ref[i] != te_ref[jnp.maximum(i - 1, 0)])

        @pl.when(first)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _tgmm(x, g, tile_expert, tiles_used, held, tile, out_dtype, interpret):
    """``dw[e] = sum over the tiles of e of x_tile^T g_tile``."""
    rows, kdim = x.shape
    n = g.shape[1]
    return pl.pallas_call(
        _tgmm_kernel,
        name="expert_tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // tile,),
            in_specs=[
                pl.BlockSpec((tile, kdim),
                             lambda i, te, used: (_used(i, used), 0)),
                pl.BlockSpec((tile, n),
                             lambda i, te, used: (_used(i, used), 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, kdim, n), lambda i, te, used: (te[_used(i, used)], 0, 0)),
            scratch_shapes=[pltpu.VMEM((kdim, n), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((held, kdim, n), out_dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert, tiles_used, x, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped(x, w, tile_expert, tiles_used, tile, interpret):
    return _gmm(x, w, tile_expert, tiles_used, tile, False, interpret)


def _grouped_fwd(x, w, tile_expert, tiles_used, tile, interpret):
    return (_gmm(x, w, tile_expert, tiles_used, tile, False, interpret),
            (x, w, tile_expert, tiles_used))


def _grouped_bwd(tile, interpret, res, g):
    x, w, tile_expert, tiles_used = res
    g = g.astype(x.dtype)
    dx = _gmm(g, w, tile_expert, tiles_used, tile, True, interpret)
    dw = _tgmm(x, g, tile_expert, tiles_used, w.shape[0], tile, w.dtype,
               interpret)
    return dx, dw, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x, w, tile_expert, tiles_used, tile: int = TILE,
                   interpret: Optional[bool] = None):
    """``x [rows, K]`` (rows grouped by :func:`group_rows`) times ``w [held,
    K, N]``: tile ``i`` of the rows against ``w[tile_expert[i]]``. Tiles past
    ``tiles_used`` are skipped and their rows of the result hold whatever
    was there: mask them (:func:`live_rows`) before anything that sums over
    rows. Differentiable in ``x`` and ``w``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _grouped(x, w, tile_expert, tiles_used, tile, interpret)


def live_rows(rows: int, tiles_used, tile: int = TILE):
    """``[rows, 1]`` bool: the rows of the tiles in use."""
    return (jnp.arange(rows) // tile < tiles_used[0])[:, None]


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------


def dropless_experts(x, gates, experts, w1, w3, w2, first: int,
                     tile: int = TILE, interpret: Optional[bool] = None):
    """The held experts' part of a SiLU-gated expert layer for the tokens
    ``x [N, h]`` routed by ``gates, experts [N, k]``: ``sum_{j: expert held}
    gates[n, j] * w2_e (silu(w1_e x_n) * w3_e x_n)``. ``w1, w3 [held, h, m]``
    and ``w2 [held, m, h]`` are the experts ``first .. first + held``. No
    pair is dropped, whatever the imbalance. Returns the output and each
    held expert's load ``[held]``."""
    held = w1.shape[0]
    lay = group_rows(experts, first, held, tile)
    mm = functools.partial(grouped_matmul, tile_expert=lay.tile_expert,
                           tiles_used=lay.tiles_used, tile=tile,
                           interpret=interpret)
    xe = dispatch(x, lay.token_of_row, lay.row_of_pair, lay.tiles_used, tile,
                  interpret)
    # a skipped tile's rows are undefined: zero them once, here; past this
    # point only the rows of the tiles in use are read
    hidden = jnp.where(
        live_rows(xe.shape[0], lay.tiles_used, tile),
        jax.nn.silu(mm(xe, w1.astype(x.dtype))) * mm(xe, w3.astype(x.dtype)),
        0).astype(x.dtype)
    out = mm(hidden, w2.astype(x.dtype))
    return combine(out, gates, lay.token_of_row, lay.row_of_pair,
                   lay.tiles_used, tile, interpret), lay.load


def dropless_experts_reference(x, gates, experts, w1, w3, w2, first: int):
    """Plain ``jnp``: every held expert over every token, gate-weighted."""
    held = w1.shape[0]
    local = experts - first
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for e in range(held):
        gate = jnp.sum(jnp.where(local == e, gates, 0.0), axis=-1)
        hid = jax.nn.silu(x @ w1[e]) * (x @ w3[e])
        out = out + gate[:, None] * (hid @ w2[e])
    return out.astype(x.dtype)
