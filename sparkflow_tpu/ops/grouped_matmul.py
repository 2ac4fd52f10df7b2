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
  tokens, gate-weighted; both directions of both are gathers.
- :func:`dropless_experts`: the layer (SiLU-gated experts) from these parts.
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
    tiles = jnp.maximum(1, (load + tile - 1) // tile)
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
# tokens -> rows -> tokens, by gathers in both directions
# ---------------------------------------------------------------------------


def _take(a, index):
    """``a[index]`` with zeros where ``index`` is ``len(a)`` (a padding row,
    a pair not here): a clamped gather and a mask, no padded copy of ``a``."""
    n = a.shape[0]
    picked = a[jnp.minimum(index, n - 1)]
    return jnp.where((index < n)[..., None], picked, 0).astype(a.dtype)


@jax.custom_vjp
def dispatch(x, token_of_row, row_of_pair):
    """``x [N, h]`` -> the rows' inputs ``[rows, h]`` (zeros on padding)."""
    return _take(x, token_of_row)


def _dispatch_fwd(x, token_of_row, row_of_pair):
    return dispatch(x, token_of_row, row_of_pair), row_of_pair


def _dispatch_bwd(row_of_pair, g):
    return jnp.sum(_take(g, row_of_pair), axis=1), None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(out, gates, token_of_row, row_of_pair):
    """The rows' outputs ``[rows, h]`` back to tokens: ``y[n] = sum_j
    gates[n, j] * out[row_of_pair[n, j]]``, a pair not here adding nothing."""
    picked = _take(out, row_of_pair)                              # [N, k, h]
    return jnp.sum(picked * gates[..., None].astype(out.dtype), axis=1)


def _combine_fwd(out, gates, token_of_row, row_of_pair):
    return (combine(out, gates, token_of_row, row_of_pair),
            (out, gates, token_of_row, row_of_pair))


def _combine_bwd(res, g):
    out, gates, token_of_row, row_of_pair = res
    rows = out.shape[0]
    picked = _take(out, row_of_pair)
    d_gates = jnp.sum(picked.astype(jnp.float32)
                      * g[:, None, :].astype(jnp.float32), axis=-1)
    gate_of_row = jnp.zeros((rows + 1,), gates.dtype).at[
        row_of_pair.reshape(-1)].set(gates.reshape(-1))[:rows]
    d_out = _take(g, token_of_row) * gate_of_row[:, None].astype(g.dtype)
    return d_out.astype(out.dtype), d_gates.astype(gates.dtype), None, None


combine.defvjp(_combine_fwd, _combine_bwd)


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


def _used(i, used):
    """A tile past the last one in use takes the last one's blocks: nothing
    new is fetched for it, and no output block changes hands."""
    return jnp.minimum(i, used[0] - 1)


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
    xe = dispatch(x, lay.token_of_row, lay.row_of_pair)
    # a skipped tile's rows are undefined: zero them once, here; past this
    # point only the rows of real pairs are read (combine gathers those)
    hidden = jnp.where(
        live_rows(xe.shape[0], lay.tiles_used, tile),
        jax.nn.silu(mm(xe, w1.astype(x.dtype))) * mm(xe, w3.astype(x.dtype)),
        0).astype(x.dtype)
    out = mm(hidden, w2.astype(x.dtype))
    return combine(out, gates, lay.token_of_row, lay.row_of_pair), lay.load


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
