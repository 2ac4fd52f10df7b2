"""``ops/block_attention.py``: the block-diffusion mask as a rule of the two
indices, the tiles the kernels visit, and the kernels (interpreted on the
CPU) against the plain ``jnp`` reference beside them, forward and backward:
the one backward kernel a row's dK and dV fit the VMEM budget for, and the
dq and dkv pair past it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkflow_tpu.ops import attention as A
from sparkflow_tpu.ops import block_attention as ba
from sparkflow_tpu.ops import sparse_attention as sa

# (L, B, tile): tiles smaller than L, so that skipped tiles occur; 48 / 16
# has a half row of three tiles, (32, 8) a block as wide as a tile (a noised
# diagonal tile with every pair visible)
SHAPES = [(32, 4, 8), (64, 8, 16), (48, 4, 16), (32, 8, 8)]


def _mask_by_loops(length, block):
    """The rule, spelt out pair by pair."""
    m = np.zeros((2 * length, 2 * length), bool)
    for p in range(2 * length):
        for s in range(2 * length):
            bp, bs = (p % length) // block, (s % length) // block
            if p < length:
                m[p, s] = s < length and bs <= bp
            else:
                m[p, s] = (s < length and bs < bp) or (s >= length
                                                       and bs == bp)
    return m


@pytest.mark.parametrize("length,block,tile", SHAPES)
def test_the_rule_is_the_issues_and_leaves_a_quarter_of_the_square(
        length, block, tile):
    mask = np.asarray(ba.visible(length, block))
    np.testing.assert_array_equal(mask, _mask_by_loops(length, block))
    assert mask.sum() == ba.visible_pairs(length, block)
    assert mask.sum() == length * length + length * block
    assert mask.diagonal().all()                   # every query sees itself
    assert not mask[:length, length:].any()        # no clean query a noised key


@pytest.mark.parametrize("order", ["qk", "kq"])
@pytest.mark.parametrize("length,block,tile", SHAPES + [(32, 4, 4)])
def test_the_kernels_visit_the_tiles_the_rule_leaves_and_no_other(
        length, block, tile, order):
    """The grid of a kernel is its schedule: each tile with a visible pair
    once, none that the rule empties, and every run (a query tile's keys, or
    a key tile's queries) in one piece with its ends flagged."""
    mask = _mask_by_loops(length, block)
    n = 2 * length // tile
    by_rule = {(qi, ki) for qi in range(n) for ki in range(n)
               if mask[qi * tile:(qi + 1) * tile,
                       ki * tile:(ki + 1) * tile].any()}
    qt, kt, first, last = ba.tile_schedule(length, block, tile, tile, order)
    visited = list(zip(qt.tolist(), kt.tolist()))
    assert len(visited) == len(by_rule) and set(visited) == by_rule
    if tile % block == 0:
        assert len(by_rule) < n * (n + 1) // 2     # fewer than causality's
    runs = qt if order == "qk" else kt
    starts = [i for i in range(len(runs)) if first[i]]
    assert [runs[i] for i in starts] == sorted(set(runs.tolist()))
    assert last.tolist() == first.tolist()[1:] + [1]
    # a noised query tile: the clean tiles before its own and the one
    # noised tile on its diagonal
    if order == "qk" and tile % block == 0 and tile > block:
        half = length // tile
        for j in range(half):
            keys = [k for q, k in visited if q == half + j]
            assert keys == list(range(j + 1)) + [half + j]


def _inputs(length, seed=0, hq=4, hkv=2, d=8, rows=2):
    r = np.random.default_rng(seed)
    mk = lambda h: jnp.asarray(r.normal(size=(rows, h, 2 * length, d)),
                               jnp.float32)
    return mk(hq), mk(hkv), mk(hkv), mk(hq)


@pytest.fixture
def split_side(monkeypatch):
    """A budget no row's dK and dV fit (``ops/sparse_attention.py`` holds
    it for both files): the backward is the dq and dkv pair."""
    monkeypatch.setattr(sa, "_FUSED_DKV_VMEM_BUDGET", 0)


@pytest.fixture(scope="module",
                params=[s + (p,) for s in SHAPES for p in ("fused", "split")],
                ids=lambda s: "L%d-B%d-T%d-%s" % s)
def both(request):
    """Outputs and gradients of the kernels and of the reference, once a
    shape and a side of the backward's budget."""
    length, block, tile, path = request.param
    q, k, v, w = _inputs(length)

    def run(fn):
        def loss(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out * w), (out, lse)
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    with pytest.MonkeyPatch.context() as patch, \
            jax.default_matmul_precision("highest"), \
            A.record_attention_paths() as paths:
        if path == "split":
            patch.setattr(sa, "_FUSED_DKV_VMEM_BUDGET", 0)
        got = run(lambda q, k, v: ba.block_attention(
            q, k, v, length, block, block_q=tile, block_k=tile))
        want = run(lambda q, k, v: ba.block_attention_reference(
            q, k, v, length, block))
    assert paths == [f"block_attention_fwd:{tile}x{tile}",
                     f"block_attention_bwd:{path}"]
    return got, want


# float32 on the CPU: the kernels sum a query's keys tile by tile under a
# running maximum, the reference all at once; 1e-5 is some ten roundings of
# values of order one
@pytest.mark.parametrize("what", ["out", "lse", "dq", "dk", "dv"])
def test_kernels_match_the_reference_forward_and_backward(both, what):
    ((_, (out, lse)), grads), ((_, (w_out, w_lse)), w_grads) = both
    got = dict(out=out, lse=lse, dq=grads[0], dk=grads[1], dv=grads[2])[what]
    want = dict(out=w_out, lse=w_lse, dq=w_grads[0], dk=w_grads[1],
                dv=w_grads[2])[what]
    assert float(jnp.max(jnp.abs(want))) > 1e-2
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("block_q,block_k", [(8, 16), (16, 8), (32, 32)])
def test_query_and_key_tiles_need_not_be_equal(block_q, block_k):
    q, k, v, _ = _inputs(32, seed=1)
    with jax.default_matmul_precision("highest"):
        out, lse = ba.block_attention(q, k, v, 32, 4, block_q=block_q,
                                      block_k=block_k)
        want, want_lse = ba.block_attention_reference(q, k, v, 32, 4)
    np.testing.assert_allclose(out, want, atol=1e-5)
    np.testing.assert_allclose(lse, want_lse, atol=1e-5)


def _kernel_grids(fn, *args):
    """The grid of each ``pallas_call`` at the top of ``fn``'s jaxpr, by the
    kernel's name (traced; nothing runs)."""
    return {e.params["name"]: e.params["grid_mapping"].grid
            for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name == "pallas_call"}


# -- the forward's key tile: twice the backward's where the half row allows ----


@pytest.mark.parametrize("order", ["qk", "kq"])
@pytest.mark.parametrize("length,block,block_q,block_k,visits", [
    (4096, 4, 512, 1024, 48),     # the sdar cell's forward
    (4096, 4, 512, 512, 80),      # its backward
    (4096, 4, 512, 2048, 32),
    (64, 8, 16, 32, 16), (48, 4, 8, 24, 24)])
def test_unequal_tiles_hold_every_visible_pairs_tile_once(
        length, block, block_q, block_k, visits, order):
    """A key tile wider than the query tile: the schedule is the tiles the
    rule leaves a pair in, each once, and no other."""
    mask = np.asarray(ba.visible(length, block))
    nq, nk = 2 * length // block_q, 2 * length // block_k
    by_rule = {(int(qi), int(ki)) for qi, ki in zip(*np.nonzero(
        mask.reshape(nq, block_q, nk, block_k).any(axis=(1, 3))))}
    qt, kt, first, last = ba.tile_schedule(length, block, block_q, block_k,
                                           order)
    visited = list(zip(qt.tolist(), kt.tolist()))
    assert len(visited) == visits == len(by_rule)
    assert set(visited) == by_rule
    runs = (qt if order == "qk" else kt).tolist()
    assert [runs[i] for i in range(visits) if first[i]] == sorted(set(runs))
    assert last.tolist() == first.tolist()[1:] + [1]


# (L, every kernel's tile by default, the forward's key tile): a half row
# of two and of three key tiles of 1 024; one 1 024 does not divide; one
# shorter than 1 024
DEFAULT_TILES = [(2048, 512, 1024), (3072, 512, 1024), (1536, 512, 512),
                 (384, 128, 128)]


@pytest.mark.parametrize("length,tile,fwd_k", DEFAULT_TILES)
def test_the_forward_at_its_own_key_tile_is_the_reference_and_the_equal_tiles(
        length, tile, fwd_k):
    """By default the forward walks the largest of 1 024, 512, 256, 128 keys
    that divides the half row and logs its tile; ``out`` and ``lse`` are the
    reference's, and the forward's at the backward's tiles, to float32's
    rounding."""
    q, k, v, _ = _inputs(length, seed=2, hq=2, hkv=1, rows=1)
    with jax.default_matmul_precision("highest"), \
            A.record_attention_paths() as paths:
        out, lse = ba.block_attention(q, k, v, length, 4)
        want, want_lse = ba.block_attention_reference(q, k, v, length, 4)
        equal, equal_lse = ba.block_attention(q, k, v, length, 4,
                                              block_q=tile, block_k=tile)
    assert paths == [f"block_attention_fwd:{tile}x{fwd_k}",
                     f"block_attention_fwd:{tile}x{tile}"]
    for got, ref in ((out, want), (lse, want_lse), (out, equal),
                     (lse, equal_lse)):
        np.testing.assert_allclose(got, ref, atol=1e-5)


# (L, B, the query tile, the forward's key tile, the backward's, Hq, Hkv)
WIDE_FORWARDS = [
    (1024, 4, 512, 1024, 512, 4, 2),  # the cells' tiles, a half row of one
    (64, 8, 16, 32, 16, 4, 2), (96, 4, 16, 32, 16, 4, 2),
    (64, 16, 16, 64, 16, 4, 2),
    # every other query tile's own key tile is half empty (the blocks after
    # its own): two clean key tiles; a key tile as wide as the half row
    (512, 4, 128, 256, 128, 8, 1), (512, 4, 128, 256, 128, 4, 2),
    (512, 8, 256, 512, 256, 8, 1), (512, 8, 256, 512, 256, 4, 2)]


# the dq and dkv pair on the cells' tiles, on toy tiles and on a group of 8
@pytest.mark.parametrize("length,block,block_q,fwd_k,bwd_k,hq,hkv,path", [
    case + ("fused",) for case in WIDE_FORWARDS] + [
    WIDE_FORWARDS[i] + ("split",) for i in (0, 1, 4)])
def test_a_forward_at_twice_the_backwards_key_tile_moves_no_gradient(
        length, block, block_q, fwd_k, bwd_k, hq, hkv, path, monkeypatch):
    """``_attend`` with the forward's ``cfg`` at ``block_q x fwd_k`` and the
    backward's at ``block_q x bwd_k``: ``out`` and ``lse`` are the
    reference's. The backward's tile did not move: dQ, dK, dV through the
    ``custom_vjp`` are, to the bit, the backward kernels' at ``block_q x
    bwd_k`` on the wide forward's ``out`` and ``lse`` (which differ from the
    equal tiles' in float32's last place, and the gradients with them), and
    the reference's."""
    if path == "split":
        monkeypatch.setattr(sa, "_FUSED_DKV_VMEM_BUDGET", 0)
    q, k, v, w = _inputs(length, seed=fwd_k, hq=hq, hkv=hkv,
                         rows=1 if length > 96 else 2)
    cfg = lambda keys: (length, block, 1.0 / np.sqrt(q.shape[-1]), block_q,
                        keys, True)
    wide = lambda *a: ba._attend(*a, cfg(fwd_k), cfg(bwd_k))
    plain = lambda *a: ba.block_attention_reference(*a, length, block)
    grads = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a)[0] * w),
                                argnums=(0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        (out, lse), (want, want_lse) = wide(q, k, v), plain(q, k, v)
        with A.record_attention_paths() as paths:
            got = grads(wide)
            same = ba._backward(q, k, v, out, lse, w, cfg(bwd_k))
        want_grads = grads(plain)
    assert paths == [f"block_attention_fwd:{block_q}x{fwd_k}",
                     f"block_attention_bwd:{path}",
                     f"block_attention_bwd:{path}"]
    np.testing.assert_allclose(out, want, atol=1e-5)
    np.testing.assert_allclose(lse, want_lse, atol=1e-5)
    for a, b, ref in zip(got, same, want_grads):
        assert float(jnp.max(jnp.abs(ref))) > 1e-2
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(a, ref, atol=1e-5)


# (L, every kernel's tile by default, the forward's key tile, the visits of
# the forward's schedule and of the backward's): the sdar cell's half row; one
# key tile of 1 024; half rows of 512 and 384 as before
RULE = [(4096, 512, 1024, 48, 80), (1024, 512, 1024, 6, 8),
        (512, 512, 512, 3, 3), (384, 128, 128, 15, 15)]


@pytest.mark.parametrize("length,tile,fwd_k,fwd_visits,bwd_visits", RULE)
def test_by_default_the_forward_walks_its_own_key_tile_of_the_half_row(
        length, tile, fwd_k, fwd_visits, bwd_visits):
    """Traced at the cell's widths (nothing runs): the rule is
    ``ops/sparse_attention._tiles`` of ``length``, ``block_attn_fwd`` runs
    over the schedule of its tile and ``block_attn_bwd_dqkv`` over the
    schedule of 512 x 512, under their names and path entries."""
    assert sa._tiles(length) == ((tile, fwd_k), (tile, tile))
    sd = jax.ShapeDtypeStruct
    q = sd((1, 32, 2 * length, 128), jnp.bfloat16)
    kv = sd((1, 4, 2 * length, 128), jnp.bfloat16)
    grad = jax.grad(lambda q, k, v: jnp.sum(ba.block_attention(
        q, k, v, length, 4)[0].astype(jnp.float32)), argnums=(0, 1, 2))
    with A.record_attention_paths() as paths:
        grids = _kernel_grids(grad, q, kv, kv)
    assert paths == [f"block_attention_fwd:{tile}x{fwd_k}",
                     "block_attention_bwd:fused"]
    assert grids == {"block_attn_fwd": (4, fwd_visits),
                     "block_attn_bwd_dqkv": (4, bwd_visits)}
    assert fwd_visits == ba.tile_schedule(length, 4, tile, fwd_k).shape[1]
    assert bwd_visits == ba.tile_schedule(length, 4, tile, tile).shape[1]


def test_an_explicit_tile_is_every_kernels():
    """``block_q=`` / ``block_k=`` mean what they meant: the forward takes
    them too, on a half row whose default would be 512 x 1 024."""
    length = 2048
    q, k, v, _ = _inputs(length, hq=2, hkv=1, rows=1)
    grad = jax.grad(lambda q: jnp.sum(ba.block_attention(
        q, k, v, length, 4, block_q=256, block_k=512)[0]))
    with A.record_attention_paths() as paths:
        grids = _kernel_grids(grad, q)
    assert paths == ["block_attention_fwd:256x512",
                     "block_attention_bwd:fused"]
    visits = ba.tile_schedule(length, 4, 256, 512).shape[1]
    assert grids == {"block_attn_fwd": (1, visits),
                     "block_attn_bwd_dqkv": (1, visits)}


def _kernel_operands(fn, *args):
    """The operands' shapes of each ``pallas_call`` in ``fn``'s jaxpr, by
    the kernel's name."""
    calls = {}

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] = [v.aval.shape for v in eqn.invars]
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (
                        value,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return calls


def _grad_of_the_sum(length, tile=8):
    return jax.grad(lambda q, k, v: jnp.sum(
        ba.block_attention(q, k, v, length, 4, block_q=tile,
                           block_k=tile)[0]), argnums=(0, 1, 2))


def test_no_mask_operand_reaches_the_kernels():
    """The mask is made in the kernel: the ``pallas_call``s take the four
    tables of the schedule, ``q``, ``k``, ``v`` (and backward ``dO`` and the
    row statistics) and nothing of ``[S, S]``. The backward is one kernel
    where a row's dK and dV fit the budget, as here."""
    length = 32
    with A.record_attention_paths() as paths:
        calls = _kernel_operands(_grad_of_the_sum(length),
                                 *_inputs(length)[:3])
    assert paths == ["block_attention_fwd:8x8", "block_attention_bwd:fused"]
    assert sorted(calls) == ["block_attn_bwd_dqkv", "block_attn_fwd"]
    for shapes in calls.values():
        assert not any(s[-2:] == (2 * length, 2 * length) for s in shapes)


def test_past_the_budget_the_backward_is_the_pair_and_takes_no_mask_either(
        split_side):
    length = 32
    with A.record_attention_paths() as paths:
        calls = _kernel_operands(_grad_of_the_sum(length),
                                 *_inputs(length)[:3])
    assert paths == ["block_attention_fwd:8x8", "block_attention_bwd:split"]
    assert sorted(calls) == ["block_attn_bwd_dkv", "block_attn_bwd_dq",
                             "block_attn_fwd"]
    for shapes in calls.values():
        assert not any(s[-2:] == (2 * length, 2 * length) for s in shapes)


# (L, B, tile): a half row of one tile (a noised query tile sees its own
# noised tile alone, or with the clean one); of four, in blocks narrower
# than a tile (the last noised tile sees every clean tile but its own's
# later blocks) and as wide (a clean tile's row of the schedule is short, a
# noised tile's diagonal whole)
BWD_SHAPES = [(16, 4, 16), (64, 4, 16), (64, 16, 16)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("length,block,tile", BWD_SHAPES)
def test_fused_backward_is_the_pairs_to_the_bit(length, block, tile, group,
                                                dtype):
    """dQ, dK, dV of ``block_attn_bwd_dqkv`` equal the dq and dkv kernels'
    in every bit (the tile function is one, ``ops/sparse_attention._bwd_tile``,
    and the ``"qk"`` schedule brings a key tile its query tiles in the
    ``"kq"`` schedule's order), and are the float32 reference's gradients to
    the operands' rounding."""
    q, k, v, w = (a.astype(dtype) for a in _inputs(
        length, seed=group, hq=2 * group, hkv=2))
    out, lse = ba.block_attention(q, k, v, length, block, block_q=tile,
                                  block_k=tile)
    bh, s, d = 2 * 2, 2 * length, q.shape[-1]
    delta = jnp.sum(w.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, group, s, 1)
    operands = ba._layout(q, k, v) + (
        w.reshape(bh, group, s, d), lse.reshape(bh, group, s, 1), delta)
    cfg = (length, block, 1.0 / np.sqrt(d), tile, tile, True)
    fused, pair = ba._bwd_fused(operands, cfg), ba._bwd_split(operands, cfg)
    want = jax.grad(lambda *a: jnp.sum(ba.block_attention_reference(
        *a, length, block)[0] * w.astype(jnp.float32)), argnums=(0, 1, 2))(
            *(a.astype(jnp.float32) for a in (q, k, v)))
    for got, other, ref in zip(fused, pair, want):
        assert got.dtype == dtype
        np.testing.assert_array_equal(got, other)
        scale = float(jnp.max(jnp.abs(ref)))
        assert scale > 1e-2
        np.testing.assert_allclose(
            got.reshape(ref.shape).astype(jnp.float32), ref,
            atol=(1e-6 if dtype == jnp.float32 else 2e-2) * max(scale, 5.0))


@pytest.mark.parametrize("bad", [dict(length=30, block=4),
                                 dict(length=24, block=3),
                                 dict(length=32, block=4, block_q=12)])
def test_shapes_that_are_no_whole_blocks_or_tiles_are_refused(bad):
    q, k, v, _ = _inputs(bad["length"] if bad["length"] % 2 == 0 else 32)
    with pytest.raises(ValueError, match="blocks of|do not divide"):
        ba.block_attention(q, k, v, **bad)
