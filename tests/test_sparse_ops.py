"""The functions of ``ops/sparse_attention.py`` and ``ops/grouped_matmul.py``
against the plain ``jnp`` references beside them, forward and backward, at
toy sizes (the pallas kernels in interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkflow_tpu.ops import attention as A
from sparkflow_tpu.ops import grouped_matmul as gm
from sparkflow_tpu.ops import sparse_attention as sa

S = 32


def _close_grads(f, g, args, atol):
    for a, b in zip(jax.grad(f, argnums=tuple(range(len(args))))(*args),
                    jax.grad(g, argnums=tuple(range(len(args))))(*args)):
        np.testing.assert_allclose(a, b, atol=atol)


@pytest.mark.parametrize("topk", [4, 8, 31])
def test_index_select_keeps_each_querys_topk(topk):
    r = np.random.default_rng(topk)
    qi = jnp.asarray(r.normal(size=(2, S, 2, 8)), jnp.float32)
    ki = jnp.asarray(r.normal(size=(2, S, 8)), jnp.float32)
    w = jnp.asarray(r.normal(size=(2, S, 2)), jnp.float32)
    # two heads' relu leaves many scores at exactly 0: ties, kept alike
    np.testing.assert_array_equal(
        sa.index_select(qi, ki, w, topk, block=8),
        sa.index_select_reference(qi, ki, w, topk))
    qi, ki = jnp.abs(qi), jnp.abs(ki)              # no two scores alike
    got = sa.index_select(qi, ki, w, topk, block=8)
    np.testing.assert_array_equal(
        got, sa.index_select_reference(qi, ki, w, topk))
    counts = np.asarray(got).sum(-1)
    np.testing.assert_array_equal(
        counts, np.broadcast_to(np.minimum(np.arange(S) + 1, topk),
                                counts.shape))


@pytest.mark.parametrize("k", [1, 5, 32])
def test_kth_largest_by_bisection_is_the_sorted_rows(k):
    x = np.random.default_rng(k).normal(size=(6, 32)).astype(np.float32)
    x[0, :4] = 0.0
    x[1, :3] = -0.0
    keys = sa._sortable(jnp.asarray(x))
    want = np.sort(np.asarray(keys), axis=-1)[:, -k]
    got = sa.kth_largest_key(lambda c: sa._count(keys >= c), k, 6)
    np.testing.assert_array_equal(got[:, 0], want)


def _indexer_inputs(seed, rows, seq, scores):
    """``qi, ki, w`` of ``rows`` rows of ``seq`` tokens. ``scores``:
    ``relu`` (two heads' relu leaves many scores at exactly 0: ties),
    ``distinct`` (no two scores alike) or ``zeros`` (a long stretch of keys
    whose scores are exactly 0 for every query, ``-0.0`` for the odd queries,
    whose other scores all lie below: the earliest keys win)."""
    r = np.random.default_rng(seed)
    qi = r.normal(size=(rows, seq, 2, 8)).astype(np.float32)
    ki = r.normal(size=(rows, seq, 8)).astype(np.float32)
    w = r.normal(size=(rows, seq, 2)).astype(np.float32)
    if scores != "relu":
        qi, ki = np.abs(qi), np.abs(ki)
    if scores == "zeros":
        ki[:, seq // 4:3 * seq // 4] *= -1
        w[:, 1::2] = -np.abs(w[:, 1::2])
    return jnp.asarray(qi), jnp.asarray(ki), jnp.asarray(w)


# (rows, S, block, topk, scores): block 8 of 32 is four query blocks over one
# tile of keys; 64 of 256 is four over two tiles of 128
SELECT_CASES = {
    "topk-below-S": (1, 32, 8, 5, "relu"),
    "topk-is-S": (1, 32, 8, 32, "relu"),
    "topk-above-S": (1, 32, 8, 64, "relu"),
    "S-not-a-multiple-of-block": (1, 40, 16, 7, "relu"),
    "two-rows": (2, 32, 8, 6, "distinct"),
    "block-straddles-topk": (1, 32, 8, 12, "distinct"),
    "block-ends-at-topk": (1, 32, 8, 16, "distinct"),
    "zeros-and-minus-zeros": (2, 32, 8, 6, "zeros"),
    "two-key-tiles": (1, 256, 64, 40, "relu"),
    "two-key-tiles-zeros": (1, 256, 64, 96, "zeros"),
    "two-key-tiles-straddled": (2, 256, 64, 100, "distinct"),
}


@pytest.mark.parametrize("case", list(SELECT_CASES))
def test_index_select_kernel_is_the_references_selection(case):
    rows, seq, block, topk, scores = SELECT_CASES[case]
    qi, ki, w = _indexer_inputs(len(case), rows, seq, scores)
    got = np.asarray(sa.index_select(qi, ki, w, topk, block=block))
    np.testing.assert_array_equal(
        got, sa.index_select_reference(qi, ki, w, topk))
    np.testing.assert_array_equal(
        got.sum(-1), np.broadcast_to(np.minimum(np.arange(seq) + 1, topk),
                                     (rows, seq)))
    if scores == "zeros":
        # the last query, an odd one, keeps the first of the zeros and
        # nothing else
        np.testing.assert_array_equal(
            got[0, seq - 1].nonzero()[0], seq // 4 + np.arange(topk))


def test_select_block_holds_minus_zero_and_zero_for_one_score():
    """In one row: ``-0.0`` before ``0.0`` before ``-0.0``, all the
    largest; the earliest are kept whatever their sign."""
    x = -np.abs(np.random.default_rng(0).normal(size=(4, 16))).astype(
        np.float32)
    x[:, 2:5], x[:, 7:9], x[:, 11:14] = -0.0, 0.0, -0.0
    got = sa.select_block(jnp.asarray(x), 12, 5)
    want = np.zeros((4, 16), bool)
    want[:, [2, 3, 4, 7, 8]] = True
    np.testing.assert_array_equal(got, want)
    idx = jax.lax.top_k(jnp.asarray(x), 5)[1]
    np.testing.assert_array_equal(np.sort(idx, axis=-1)[0], [2, 3, 4, 7, 8])


@pytest.mark.parametrize("seq", [8, 13, 32, 256])
def test_a_selection_comes_back_from_its_bits(seq):
    """An eighth of the bytes (the queries padded to a multiple of eight),
    and every 0 and 1 where it was."""
    mask = jnp.asarray(np.random.default_rng(seq).random((2, seq, seq)) < 0.3,
                       jnp.int8)
    packed = sa.pack_selection(mask)
    assert packed.shape == (2, -(-seq // 8), seq) and packed.dtype == jnp.uint8
    back = jax.jit(sa.unpack_selection)(packed)
    assert back.dtype == jnp.int8
    np.testing.assert_array_equal(back, mask)


def _attention_inputs(seed, hq=4, hkv=2, seq=S, d=8, keep=0.4):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(2, hq, seq, d)), jnp.float32)
    k = jnp.asarray(r.normal(size=(2, hkv, seq, d)), jnp.float32)
    v = jnp.asarray(r.normal(size=(2, hkv, seq, d)), jnp.float32)
    mask = np.tril(r.random((2, seq, seq)) < keep)
    mask[:, np.arange(seq), np.arange(seq)] = True     # a key for every query
    return q, k, v, jnp.asarray(mask, jnp.int8)


@pytest.fixture(params=["fused", "split"])
def bwd_path(request, monkeypatch):
    """Both sides of ``_bwd_is_fused``: the budget as it is, where every toy
    row fits and the backward is one kernel, and a budget no row fits, where
    it is the dq and dkv pair."""
    if request.param == "split":
        monkeypatch.setattr(sa, "_FUSED_DKV_VMEM_BUDGET", 0)
    return request.param


@pytest.mark.parametrize("hq,hkv,block", [(4, 2, None), (4, 4, 16), (8, 1, 8)])
def test_selected_attention_matches_its_reference(hq, hkv, block, bwd_path):
    q, k, v, mask = _attention_inputs(hq * 10 + hkv, hq, hkv)
    kernel = lambda q, k, v: sa.selected_attention(
        q, k, v, mask, block_q=block, block_k=block)
    plain = lambda q, k, v: sa.selected_attention_reference(q, k, v, mask)
    (out, lse), (want, want_lse) = kernel(q, k, v), plain(q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-6)
    np.testing.assert_allclose(lse, want_lse, atol=2e-6)
    tilt = jnp.asarray(np.random.default_rng(0).normal(size=out.shape),
                       jnp.float32)
    with A.record_attention_paths() as paths:
        _close_grads(lambda *a: jnp.sum(kernel(*a)[0] * tilt),
                     lambda *a: jnp.sum(plain(*a)[0] * tilt), (q, k, v), 5e-6)
    tile = block or S
    assert paths == [f"sparse_attention_fwd:{tile}x{tile}",
                     f"sparse_attention_bwd:{bwd_path}"]


def _kernel_grids(fn, *args):
    """The grid of each ``pallas_call`` at the top of ``fn``'s jaxpr, by the
    kernel's name (traced; nothing runs)."""
    return {e.params["name"]: e.params["grid_mapping"].grid
            for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name == "pallas_call"}


# -- the forward's key tile: twice the backward's where the row allows ---------

# (row, every kernel's tile by default, the forward's key tile): two and
# three key tiles of 1 024; a row 1 024 does not divide; a row shorter than
# 1 024; then the rule alone: a row of one key tile of 1 024, both MoE
# cells' 8 192, rows of 512 and 384 as before, a row nothing divides
DEFAULT_TILES = [(2048, 512, 1024), (3072, 512, 1024), (1536, 512, 512),
                 (768, 256, 256), (1024, 512, 1024), (8192, 512, 1024),
                 (512, 512, 512), (384, 128, 128), (40, 40, 40)]


@pytest.mark.parametrize("seq,tile,fwd_k", DEFAULT_TILES)
def test_the_forwards_key_tile_is_the_rows_largest_up_to_1024(seq, tile,
                                                              fwd_k):
    """One rule from the row's length: the backward's tiles are what every
    kernel's were, the forward's key tile the largest of 1 024, 512, 256, 128
    that divides the row. Tiles a caller names are every kernel's."""
    assert sa._tiles(seq) == ((tile, fwd_k), (tile, tile))
    assert sa._tiles(seq, 128, 256) == ((128, 256), (128, 256))
    assert sa._tiles(seq, block_k=128) == ((tile, 128), (tile, 128))
    assert sa._tiles(seq, block_q=128) == ((128, fwd_k), (128, tile))
    # every other kernel's default is what it was
    assert sa._block(8192) == 512 and sa._block(8192, 128) == 128
    assert sa._index_blocks(8192, 256)[:2] == (256, 512)


@pytest.mark.parametrize("seq", [1024, 8192])
def test_by_default_the_kernels_grids_are_512_x_1024_forward_and_512_x_512_back(
        seq):
    """Traced at the cells' widths (nothing runs): ``sparse_attn_fwd`` on
    ``seq / 512 x seq / 1024`` visits a KV head, ``sparse_attn_bwd_dqkv`` on
    ``seq / 512`` squared, under their names and path entries."""
    sd = jax.ShapeDtypeStruct
    q, kv = sd((1, 32, seq, 128), jnp.bfloat16), sd((1, 4, seq, 128),
                                                    jnp.bfloat16)
    grad = jax.grad(lambda q, k, v, mask: jnp.sum(sa.selected_attention(
        q, k, v, mask)[0].astype(jnp.float32)), argnums=(0, 1, 2))
    with A.record_attention_paths() as paths:
        grids = _kernel_grids(grad, q, kv, kv, sd((1, seq, seq), jnp.int8))
    assert paths == ["sparse_attention_fwd:512x1024",
                     "sparse_attention_bwd:fused"]
    assert grids == {"sparse_attn_fwd": (4, seq // 512, seq // 1024),
                     "sparse_attn_bwd_dqkv": (4, seq // 512, seq // 512)}


@pytest.mark.parametrize("seq,tile,fwd_k", DEFAULT_TILES[:4])
def test_the_forward_at_its_own_key_tile_is_the_reference_and_the_equal_tiles(
        seq, tile, fwd_k):
    """By default the forward walks ``_tiles``' keys a visit and logs
    its tile; ``out`` and ``lse`` are the reference's, and the forward's at
    the backward's tiles, to float32's rounding (the tile changes the order
    of the online softmax's rescaling and nothing else)."""
    q, k, v, mask = _attention_inputs(seq, 2, 1, seq)
    q, k, v, mask = q[:1], k[:1], v[:1], mask[:1]
    with A.record_attention_paths() as paths:
        out, lse = sa.selected_attention(q, k, v, mask)
    assert paths == [f"sparse_attention_fwd:{tile}x{fwd_k}"]
    want, want_lse = sa.selected_attention_reference(q, k, v, mask)
    equal, equal_lse = sa._forward(q, k, v, mask, 1.0 / np.sqrt(q.shape[-1]),
                                   tile, tile, True)
    for got, ref in ((out, want), (lse, want_lse), (out, equal),
                     (lse, equal_lse)):
        np.testing.assert_allclose(got, ref, atol=2e-6)


# (row, the query tile, the forward's key tile, the backward's, Hq, Hkv)
WIDE_FORWARDS = [
    (2048, 512, 1024, 512, 4, 2),     # both MoE cells' tiles, four query tiles
    (64, 16, 32, 16, 4, 2), (96, 16, 32, 16, 4, 2),
    (64, 16, 64, 16, 4, 2),           # a key tile four times the backward's
    # every other query tile's last key tile is half above the diagonal
    (512, 128, 256, 128, 8, 1), (512, 128, 256, 128, 4, 2),
    (1024, 256, 512, 256, 8, 1), (1024, 256, 512, 256, 4, 2)]


# the dq and dkv pair on the cells' tiles, on toy tiles and on a group of 8
@pytest.mark.parametrize("seq,block_q,fwd_k,bwd_k,hq,hkv,bwd_path", [
    case + ("fused",) for case in WIDE_FORWARDS] + [
    WIDE_FORWARDS[i] + ("split",) for i in (0, 1, 4)])
def test_a_forward_at_twice_the_backwards_key_tile_moves_no_gradient(
        seq, block_q, fwd_k, bwd_k, hq, hkv, bwd_path, monkeypatch):
    """``_selected`` with the forward at ``block_q x fwd_k`` and the backward
    at ``block_q x bwd_k`` (``_visible`` and ``_last_tile`` on a rectangle):
    ``out`` and ``lse`` are the reference's. The backward's tile did not
    move: dQ, dK, dV through the ``custom_vjp`` are, to the bit, the backward
    kernels' at ``block_q x bwd_k`` on the wide forward's ``out`` and ``lse``
    (these differ from the equal tiles' in float32's last place, another
    order of the online softmax's sums, and the gradients with them), and
    the reference's."""
    if bwd_path == "split":
        monkeypatch.setattr(sa, "_FUSED_DKV_VMEM_BUDGET", 0)
    q, k, v, mask = _attention_inputs(seq + fwd_k, hq, hkv, seq)
    if seq > 64:
        q, k, v, mask = q[:1], k[:1], v[:1], mask[:1]
    tilt = jnp.asarray(np.random.default_rng(1).normal(size=q.shape),
                       jnp.float32)
    scale = 1.0 / np.sqrt(q.shape[-1])
    wide = lambda *a: sa._selected(*a, mask, scale, (block_q, fwd_k),
                                   (block_q, bwd_k), True)
    plain = lambda *a: sa.selected_attention_reference(*a, mask)
    grads = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a)[0] * tilt),
                                argnums=(0, 1, 2))(q, k, v)
    (out, lse), (want, want_lse) = wide(q, k, v), plain(q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-6)
    np.testing.assert_allclose(lse, want_lse, atol=2e-6)
    with A.record_attention_paths() as paths:
        got = grads(wide)
        same = sa._backward(q, k, v, mask, out, lse, tilt, scale, block_q,
                            bwd_k, True)
    assert paths == [f"sparse_attention_fwd:{block_q}x{fwd_k}",
                     f"sparse_attention_bwd:{bwd_path}",
                     f"sparse_attention_bwd:{bwd_path}"]
    for a, b, ref in zip(got, same, grads(plain)):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(a, ref, atol=5e-6)


def test_an_explicit_tile_is_every_kernels():
    """``block_q=`` / ``block_k=`` mean what they meant: the forward takes
    them too, on a row whose default would be 512 x 1 024."""
    q, k, v, mask = _attention_inputs(7, 2, 1, 2048)
    grad = jax.grad(lambda q: jnp.sum(sa.selected_attention(
        q, k[:1], v[:1], mask[:1], block_q=256, block_k=512)[0]))
    with A.record_attention_paths() as paths:
        grids = _kernel_grids(grad, q[:1])
    assert paths == ["sparse_attention_fwd:256x512",
                     "sparse_attention_bwd:fused"]
    assert grids == {"sparse_attn_fwd": (1, 8, 4),
                     "sparse_attn_bwd_dqkv": (1, 8, 4)}


# -- the selected-key attention's backward: one kernel, or the pair ------------


def _flat_backward_operands(q, k, v, mask, tile, seed=0):
    """What ``_bwd_fused`` and ``_bwd_split`` take: the forward's layout,
    its output's ``delta`` against a random ``dO``, and the rest of their
    arguments."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    out, lse = sa.selected_attention(q, k, v, mask, block_q=tile,
                                     block_k=tile)
    g = jnp.asarray(np.random.default_rng(seed).normal(size=q.shape), q.dtype)
    shape_q = (b * hkv, hq // hkv, s, d)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(shape_q[:3] + (1,))
    flat = (q.reshape(shape_q), k.reshape(b * hkv, s, d),
            v.reshape(b * hkv, s, d), g.reshape(shape_q),
            lse.reshape(shape_q[:3] + (1,)), delta, mask, hkv,
            1.0 / np.sqrt(d), tile, tile, True)
    return flat, g


# which keys a query selects: two in five of those before it; all of them
# (a row's last query tile has something in every key tile); its own and the
# row's first alone (the tiles between are visited and hold nothing)
KEEPS = {"some": 0.4, "all": 1.0, "ends": 0.0}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("seq,tile,keep", [
    (16, 16, "some"), (64, 16, "some"), (64, 16, "all"), (64, 16, "ends")])
def test_fused_backward_is_the_pairs_to_the_bit(seq, tile, keep, group,
                                                dtype):
    """dQ, dK, dV of ``sparse_attn_bwd_dqkv`` equal the dq and dkv kernels'
    in every bit (one tile function, which sums a group's terms of dK and dV
    before it adds them to the accumulator, in either kernel; a key tile's
    query tiles come in the same order on both grids), and both are the
    float32 reference's gradients to the operands' rounding."""
    q, k, v, mask = _attention_inputs(seq + group, 2 * group, 2, seq,
                                      keep=KEEPS[keep])
    mask = mask.at[:, :, 0].set(1)
    flat, g = _flat_backward_operands(*(a.astype(dtype) for a in (q, k, v)),
                                      mask, tile)
    fused, pair = sa._bwd_fused(*flat), sa._bwd_split(*flat)
    want = jax.grad(lambda *a: jnp.sum(sa.selected_attention_reference(
        *a, mask)[0] * g.astype(jnp.float32)), argnums=(0, 1, 2))(
            *(a.astype(dtype).astype(jnp.float32) for a in (q, k, v)))
    for got, other, ref in zip(fused, pair, want):
        assert got.dtype == dtype
        np.testing.assert_array_equal(got, other)
        scale = float(jnp.max(jnp.abs(ref)))
        assert scale > 1e-2
        np.testing.assert_allclose(
            got.reshape(ref.shape).astype(jnp.float32), ref,
            atol=(1e-6 if dtype == jnp.float32 else 2e-2) * max(scale, 5.0))


def _kernels_of(fn, *args):
    """The names of the ``pallas_call``s in ``fn``'s jaxpr, sorted."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (
                        value,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return sorted(names)


def test_the_backward_is_one_kernel_where_the_row_fits_and_the_pair_past_it(
        bwd_path):
    q, k, v, mask = _attention_inputs(3)
    grad = jax.grad(lambda q, k, v: jnp.sum(sa.selected_attention(
        q, k, v, mask)[0]), argnums=(0, 1, 2))
    with A.record_attention_paths() as paths:
        kernels = _kernels_of(grad, q, k, v)
    assert paths == [f"sparse_attention_fwd:{S}x{S}",
                     f"sparse_attention_bwd:{bwd_path}"]
    assert kernels == dict(
        fused=["sparse_attn_bwd_dqkv", "sparse_attn_fwd"],
        split=["sparse_attn_bwd_dkv", "sparse_attn_bwd_dq",
               "sparse_attn_fwd"])[bwd_path]


@pytest.mark.parametrize("s,d,dtype,fused", [
    (8192, 128, jnp.bfloat16, True),      # both MoE cells: 16 MiB of the 32
    (8192, 128, jnp.float32, True),       # 24 MiB
    (8192, 64, jnp.bfloat16, True),       # a head of 64 pads to the 128 lanes
    (16384, 128, jnp.bfloat16, True),     # at the budget
    (16384, 128, jnp.float32, False),     # 48 MiB
    (32768, 128, jnp.bfloat16, False)])   # 64 MiB
def test_the_fused_backwards_budget_is_the_rows_accumulators_and_outputs(
        s, d, dtype, fused):
    """The predicate both files ask: two float32 accumulators and two
    double-buffered output blocks of a KV head's ``[s, d]``, ``d`` padded to
    the lanes, against 32 MiB."""
    assert sa._bwd_is_fused(s, d, dtype) == fused
    lanes = -(-d // 128) * 128
    held = 2 * s * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)
    assert (held <= 32 * 1024 * 1024) == fused


@pytest.mark.parametrize("block", [None, 16])
def test_selected_probs_sum_to_one_over_each_selection(block):
    q, k, v, mask = _attention_inputs(5)
    _, lse = sa.selected_attention(q, k, v, mask)
    got = sa.selected_probs(q, k, lse, mask, block_q=block, block_k=block)
    np.testing.assert_allclose(
        got, sa.selected_probs_reference(q, k, lse, mask), atol=1e-6)
    np.testing.assert_allclose(jnp.sum(got, axis=-1), 1.0, atol=1e-5)
    assert float(jnp.max(jnp.where(mask != 0, 0.0, got))) == 0.0


# (S, block, topk, the target's keys set to zero)
LOSS_CASES = {
    "toy": (32, 8, 0, None),
    "fewer-than-topk-and-a-target-zero-in-part": (32, 8, 12, slice(4, 20)),
    "S-not-a-multiple-of-block": (40, 16, 9, None),
    "two-key-tiles": (256, 64, 48, slice(100, 160)),
}


@pytest.mark.parametrize("case", list(LOSS_CASES))
def test_indexer_loss_matches_its_reference_forward_and_backward(case):
    """The selection is the indexer's own where ``topk`` is given (early
    queries then hold fewer than ``topk`` keys), else a random one."""
    seq, block, topk, zeroed = LOSS_CASES[case]
    qi, ki, w = _indexer_inputs(8, 2, seq, "relu")
    q, k, v, mask = _attention_inputs(9, seq=seq)
    if topk:
        mask = sa.index_select(qi, ki, w, topk, block=block)
    _, lse = sa.selected_attention(q, k, v, mask)
    target = sa.selected_probs(q, k, lse, mask)
    if zeroed is not None:
        target = target.at[:, :, zeroed].set(0.0)
    tilt = jnp.asarray([1.0, -0.5])
    blocked = lambda *a: jnp.sum(
        tilt * sa.indexer_loss(*a, mask, target, block=block))
    plain = lambda *a: jnp.sum(
        tilt * sa.indexer_loss_reference(*a, mask, target))
    np.testing.assert_allclose(
        sa.indexer_loss(qi, ki, w, mask, target, block=block),
        sa.indexer_loss_reference(qi, ki, w, mask, target), rtol=1e-6)
    assert float(sa.indexer_loss(qi, ki, w, mask, target,
                                 block=block)[0]) > 0
    _close_grads(blocked, plain, (qi, ki, w), 1e-6)


def _routed(seed, n=24, e=8, k=2, first=2, held=4, h=16, m=8):
    r = np.random.default_rng(seed)
    logits = jnp.asarray(r.normal(size=(n, e)), jnp.float32)
    _, gates, experts = gm.route_top_k(logits, k)
    x = jnp.asarray(r.normal(size=(n, h)), jnp.float32)
    w = [jnp.asarray(r.normal(size=s) * 0.3, jnp.float32)
         for s in ((held, h, m), (held, h, m), (held, m, h))]
    return x, gates, experts, w, first


@pytest.mark.parametrize("tile", [8, 16])
def test_grouped_matmul_matches_its_reference(tile):
    x, gates, experts, (w1, _, _), first = _routed(tile)
    lay = gm.group_rows(experts, first, w1.shape[0], tile)
    xe = gm.dispatch(x, lay.token_of_row, lay.row_of_pair, lay.tiles_used,
                     tile)
    live = gm.live_rows(xe.shape[0], lay.tiles_used, tile)
    xe = jnp.where(live, xe, 0)
    args = (lay.tile_expert, lay.tiles_used, tile)
    kernel = lambda a, b: jnp.where(live, gm.grouped_matmul(a, b, *args), 0)
    plain = lambda a, b: gm.grouped_matmul_reference(a, b, *args)
    np.testing.assert_allclose(kernel(xe, w1), plain(xe, w1), atol=1e-6)
    assert int(lay.tiles_used[0]) < xe.shape[0] // tile   # tiles are skipped
    # a skipped tile's rows of the input's gradient are undefined too
    grads = lambda f: jax.grad(lambda a, b: jnp.sum(jnp.sin(f(a, b))),
                               argnums=(0, 1))(xe, w1)
    (dx, dw), (dx_want, dw_want) = grads(kernel), grads(plain)
    np.testing.assert_allclose(jnp.where(live, dx, 0), dx_want, atol=2e-6)
    np.testing.assert_allclose(dw, dw_want, atol=2e-6)


@pytest.mark.parametrize("first,held", [(0, 8), (2, 4), (6, 2)])
def test_dropless_experts_match_their_reference(first, held):
    x, gates, experts, w, _ = _routed(first + held, first=first, held=held)
    kernel = lambda x, g, *w: gm.dropless_experts(x, g, experts, *w, first,
                                                  tile=8)[0]
    plain = lambda x, g, *w: gm.dropless_experts_reference(x, g, experts, *w,
                                                           first)
    np.testing.assert_allclose(kernel(x, gates, *w), plain(x, gates, *w),
                               atol=2e-6)
    load = gm.dropless_experts(x, gates, experts, *w, first, tile=8)[1]
    want = [(np.asarray(experts) == first + e).sum() for e in range(held)]
    np.testing.assert_array_equal(load, want)
    _close_grads(lambda *a: jnp.sum(jnp.sin(kernel(*a))),
                 lambda *a: jnp.sum(jnp.sin(plain(*a))), (x, gates, *w), 5e-6)


# -- the experts' rows: tokens -> rows -> tokens over the tiles in use --------

TILE = 8
SHARES = {"one_tile": (14, 2, -1.5), "a_fifth": (0, 2, 0.0),
          "all_rows": (0, 16, 0.0)}


def _laid_out(share, dtype, n=96, k=2, h=32, seed=0):
    """A routing over 16 experts with ``held`` of them here from ``first``
    (``SHARES``: the pairs here fill one tile an expert, about a fifth of
    the buffer, all of it; the third number leans the router towards or
    away from the held experts), its layout, tokens ``x``, gates, and a
    buffer of rows' outputs poisoned past the tiles in use."""
    first, held, lean = SHARES[share]
    r = np.random.default_rng(seed)
    logits = jnp.asarray(r.normal(size=(n, 16)), jnp.float32)
    logits = logits.at[:, first:first + held].add(lean)
    _, gates, experts = gm.route_top_k(logits, k)
    lay = gm.group_rows(experts, first, held, TILE)
    rows = lay.token_of_row.shape[0]
    live = gm.live_rows(rows, lay.tiles_used, TILE)
    x = jnp.asarray(r.normal(size=(n, h)), dtype)
    out = jnp.where(live, jnp.asarray(r.normal(size=(rows, h)), dtype),
                    jnp.nan)
    return lay, live, x, gates, out


def _where(lay):
    return lay.token_of_row, lay.row_of_pair, lay.tiles_used, TILE


def _plain_combine(out, gates, lay):
    picked = gm._take(out, lay.row_of_pair).astype(jnp.float32)
    return jnp.sum(picked * gates[..., None], axis=1).astype(out.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("share", list(SHARES))
def test_rows_from_tokens_are_the_plain_gather_where_defined(share, dtype):
    lay, live, x, gates, _ = _laid_out(share, dtype)
    used = int(lay.tiles_used[0]) * TILE
    assert {"one_tile": used == 2 * TILE,
            "a_fifth": 0.1 < used / live.shape[0] < 0.3,
            # every pair is here; the bound keeps a spare tile an expert
            "all_rows": used >= 0.75 * live.shape[0]
            and int(lay.load.sum()) == gates.size}[share]
    got = gm.dispatch(x, *_where(lay))
    want = gm._take(x, lay.token_of_row)
    np.testing.assert_array_equal(np.asarray(got[:used], np.float32),
                                  np.asarray(want[:used], np.float32))
    # a padding row of a tile in use (an index equal to the length): zeros
    padding = np.asarray(lay.token_of_row[:used]) == x.shape[0]
    assert padding.any()
    assert not np.asarray(got[:used], np.float32)[padding].any()
    # with each row's gate as a multiplier, and each row's product with
    # another buffer whose rows past the tiles in use are NaN
    scale = gm._gate_of_row(gates, lay.row_of_pair, live.shape[0])
    other = jnp.where(live, 1.5, jnp.nan).astype(dtype) * jnp.ones_like(got)
    scaled, dots = gm._rows_in(x, lay.token_of_row, lay.tiles_used, TILE,
                               True, scale=scale, dot_with=other)
    np.testing.assert_allclose(
        np.asarray(scaled[:used], np.float32),
        np.asarray((want.astype(jnp.float32) * scale).astype(dtype)[:used],
                   np.float32))
    np.testing.assert_allclose(
        dots[:used, 0], 1.5 * jnp.sum(want.astype(jnp.float32), -1)[:used],
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("share", list(SHARES))
def test_tokens_from_rows_read_the_tiles_in_use_alone(share, dtype):
    lay, live, x, gates, out = _laid_out(share, dtype, seed=1)
    clean = jnp.where(live, out, 0)
    eps = 1e-6 if dtype == jnp.float32 else 1e-2
    # NaN past the tiles in use: a read there would show in every sum
    np.testing.assert_allclose(
        np.asarray(gm.combine(out, gates, *_where(lay)), np.float32),
        np.asarray(_plain_combine(clean, gates, lay), np.float32),
        atol=eps, rtol=eps)
    np.testing.assert_allclose(
        np.asarray(gm._rows_out(out, lay.token_of_row, lay.tiles_used,
                                x.shape[0], TILE, True), np.float32),
        np.asarray(jnp.sum(gm._take(clean, lay.row_of_pair).astype(
            jnp.float32), axis=1).astype(dtype), np.float32),
        atol=eps, rtol=eps)


def test_an_index_equal_to_the_length_gives_zeros():
    x = jnp.arange(1.0, 49.0).reshape(12, 4)
    index = jnp.asarray([3, 12, 0, 12, 12, 11, 12, 5], jnp.int32)
    used = jnp.ones((1,), jnp.int32)
    got = gm._rows_in(x, index, used, TILE, True)[0]
    np.testing.assert_array_equal(got, gm._take(x, index))
    assert not np.asarray(got)[np.asarray(index) == 12].any()
    back = gm._rows_out(got + 1.0, index, used, 12, TILE, True)
    want = np.zeros((12, 4), np.float32)
    for r, t in enumerate(np.asarray(index)):
        if t < 12:
            want[t] += np.asarray(got[r]) + 1.0
    np.testing.assert_array_equal(back, want)


@pytest.mark.parametrize("share", list(SHARES))
def test_dispatch_and_combine_gradients_match_the_plain_forms(share):
    lay, live, x, gates, out = _laid_out(share, jnp.float32, seed=2)
    r = np.random.default_rng(3)
    tilt_rows = jnp.asarray(r.normal(size=out.shape), jnp.float32)
    tilt = jnp.asarray(r.normal(size=x.shape), jnp.float32)
    _close_grads(
        lambda a: jnp.sum(jnp.where(live, gm.dispatch(a, *_where(lay)), 0)
                          * tilt_rows),
        lambda a: jnp.sum(gm._take(a, lay.token_of_row)
                          * jnp.where(live, tilt_rows, 0)), (x,), 1e-6)
    clean = jnp.where(live, out, 0)
    kernel = jax.grad(lambda o, g: jnp.sum(
        jnp.sin(gm.combine(o, g, *_where(lay))) * tilt), argnums=(0, 1))
    plain = jax.grad(lambda o, g: jnp.sum(
        jnp.sin(_plain_combine(o, g, lay)) * tilt), argnums=(0, 1))
    (d_out, d_gates), (d_out_want, d_gates_want) = (kernel(out, gates),
                                                    plain(clean, gates))
    np.testing.assert_allclose(jnp.where(live, d_out, 0), d_out_want,
                               atol=1e-6)
    np.testing.assert_allclose(d_gates, d_gates_want, atol=2e-6)
    assert float(jnp.abs(d_gates_want).max()) > 0.1


@pytest.mark.parametrize("first,held", [(0, 128), (0, 16)])
def test_dropless_experts_of_128_match_their_reference(first, held):
    """Every expert held (every pair here, every row in use) and one chip's
    sixteenth-to-an-eighth share: the same kernels, whose work follows
    ``tiles_used`` and the indices."""
    r = np.random.default_rng(held)
    n, k, h, m = 32, 8, 16, 8
    _, gates, experts = gm.route_top_k(
        jnp.asarray(r.normal(size=(n, 128)), jnp.float32), k)
    x = jnp.asarray(r.normal(size=(n, h)), jnp.float32)
    w = [jnp.asarray(r.normal(size=s) * 0.3, jnp.float32)
         for s in ((held, h, m), (held, h, m), (held, m, h))]
    kernel = lambda x, g, *w: gm.dropless_experts(x, g, experts, *w, first,
                                                  tile=TILE)[0]
    plain = lambda x, g, *w: gm.dropless_experts_reference(x, g, experts, *w,
                                                           first)
    np.testing.assert_allclose(kernel(x, gates, *w), plain(x, gates, *w),
                               atol=2e-6)
    _close_grads(lambda *a: jnp.sum(jnp.sin(kernel(*a))),
                 lambda *a: jnp.sum(jnp.sin(plain(*a))), (x, gates, *w), 5e-6)


@pytest.mark.parametrize("share", list(SHARES))
def test_rows_live_counts_the_tiles_in_use(share):
    lay, live, x, _, _ = _laid_out(share, jnp.float32)
    held = SHARES[share][1]
    got = int(gm.rows_live(lay.load, TILE))
    assert got == int(lay.tiles_used[0]) * TILE == int(live.sum())
    assert got <= gm.rows_bound(x.shape[0], 2, held, TILE) == live.shape[0]
    # by layer and row, as the models count it
    np.testing.assert_array_equal(
        gm.rows_live(jnp.stack([lay.load, 0 * lay.load]), TILE),
        [got, held * TILE])
