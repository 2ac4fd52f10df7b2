"""Attention kernels: flash (interpret mode on CPU) and ring vs reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from sparkflow_tpu.ops import attention_reference, flash_attention, ring_attention


@pytest.fixture(scope="module")
def qkv():
    rs = np.random.RandomState(0)
    shape = (2, 2, 256, 64)
    return tuple(jnp.asarray(rs.randn(*shape), jnp.float32) for _ in range(3))


def test_flash_matches_reference(qkv):
    q, k, v = qkv
    ref = attention_reference(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


def test_flash_causal_matches_reference(qkv):
    q, k, v = qkv
    ref = attention_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


def test_flash_attention_differentiable(qkv):
    """Regression: pallas kernels have no autodiff rule; the custom VJP must
    give reference-exact gradients (this crashed BERT training when missing)."""
    q, k, v = qkv
    for causal in (False, True):
        gf = jax.grad(lambda a, b, c: flash_attention(
            a, b, c, causal=causal, interpret=True).sum(), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: attention_reference(
            a, b, c, causal=causal).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_flash_kv_mask_matches_reference(qkv):
    """The kernel's key-padding mask path (fwd + bwd) vs additive-mask ref."""
    q, _, _ = qkv
    rs = np.random.RandomState(7)
    mask = jnp.asarray((rs.rand(2, 256) > 0.3).astype(np.float32))

    def ref(qq):
        s = jnp.einsum("bhqd,bhkd->bhqk", qq, qq) / np.sqrt(qq.shape[-1])
        s = jnp.where(mask[:, None, None, :] > 0, s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), qq)

    out = flash_attention(q, q, q, kv_mask=mask, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q)), atol=1e-4)
    gf = jax.grad(lambda a: flash_attention(a, a, a, kv_mask=mask,
                                            interpret=True).sum())(q)
    gr = jax.grad(lambda a: ref(a).sum())(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=1e-3)


def test_flash_fallback_odd_shapes():
    """Non-tiling sequences take the jnp path and still match."""
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(1, 2, 100, 32), jnp.float32)
    out = flash_attention(q, q, q)
    ref = attention_reference(q, q, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ring_attention_matches_reference(dp_mesh):
    """Ring attention over an 8-way sp ring == plain attention."""
    from jax.sharding import Mesh
    devs = np.array(jax.devices())
    mesh = Mesh(devs.reshape(8), ("sp",))
    rs = np.random.RandomState(2)
    B, H, S, D = 2, 2, 64, 16
    q = jnp.asarray(rs.randn(B, H, S, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, H, S, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, H, S, D), jnp.float32)

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp"),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
        check_vma=False)
    out = jax.jit(ring)(q, k, v)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ring_attention_causal(dp_mesh):
    from jax.sharding import Mesh
    devs = np.array(jax.devices())
    mesh = Mesh(devs.reshape(8), ("sp",))
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(1, 2, 64, 16), jnp.float32)

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
        check_vma=False)
    out = jax.jit(ring)(q, q, q)
    ref = attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_force_xla_attention_skips_pallas(monkeypatch):
    """Sharded-jit programs must not hit the pallas kernel (no GSPMD
    partitioning rule); the guard context routes to the blockwise path."""
    import jax.numpy as jnp
    import pytest
    from sparkflow_tpu.ops import attention as A

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 1, 128, 8), jnp.float32)

    def boom(*a, **k):
        raise RuntimeError("pallas path taken")

    monkeypatch.setattr(A, "_flash", boom)
    # tiling-eligible shape: without the guard the kernel is attempted...
    with pytest.raises(RuntimeError, match="pallas path taken"):
        A.flash_attention(q, q, q)
    # ...and inside the guard context the XLA blockwise path runs instead
    with A.force_xla_attention():
        out = A.flash_attention(q, q, q)
    ref = A.attention_reference(q, q, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_last_attention_path_instrumentation():
    """chip_smoke.py and test_tpu_compile.py assert the perf path via
    last_attention_path(); pin that the recorder distinguishes pallas /
    blockwise / reference routing."""
    import jax.numpy as jnp
    from sparkflow_tpu.ops import attention as A

    if A.pltpu is None:
        pytest.skip("pallas tpu backend unimportable in this build")
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 1, 128, 8), jnp.float32)

    A.flash_attention(q, q, q, interpret=True)  # tiling-eligible
    assert A.last_attention_path() == "pallas"

    with A.force_xla_attention():
        A.flash_attention(q, q, q)
    assert A.last_attention_path() == "blockwise"

    # odd head_dim breaks the d % 8 tile rule -> dense reference fallback
    qo = jnp.asarray(rs.randn(1, 1, 128, 6), jnp.float32)
    A.flash_attention(qo, qo, qo)
    assert A.last_attention_path() == "reference"


def test_flash_bwd_nonuniform_cotangent(qkv):
    """The pallas backward kernels (dq/dk/dv) under a structured cotangent —
    uniform .sum() grads can hide transposition errors."""
    q, k, v = qkv
    rs = np.random.RandomState(9)
    w = jnp.asarray(rs.randn(*q.shape), jnp.float32)
    for causal in (False, True):
        gf = jax.grad(lambda a, b, c: (flash_attention(
            a, b, c, causal=causal, interpret=True) * w).sum(),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: (attention_reference(
            a, b, c, causal=causal) * w).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3)


def test_flash_bwd_bf16():
    rs = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rs.randn(1, 2, 128, 64), jnp.bfloat16)
               for _ in range(3))
    gf = jax.grad(lambda a, b, c: flash_attention(
        a, b, c, interpret=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: attention_reference(
        a, b, c).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=0.15)


def _flash_bwd_case(s, sk, d, dtype, causal, mask, block=128):
    """Flat operands of one backward pass, and the reference's gradients
    under the same non-uniform cotangent."""
    from sparkflow_tpu.ops import attention as A

    b, h = 2, 2
    rs = np.random.RandomState(11)
    q = jnp.asarray(rs.randn(b, h, s, d), dtype)
    k, v = (jnp.asarray(rs.randn(b, h, sk, d), dtype) for _ in range(2))
    g = jnp.asarray(rs.randn(b, h, s, d), dtype)
    # key 0 stays: a causal row with no key left is the reference's garbage
    kv_mask = (jnp.asarray((rs.rand(b, sk) > 0.3).astype(np.float32))
               .at[:, 0].set(1.0) if mask else None)
    scale = 1.0 / np.sqrt(d)
    out, lse = A._flash_pallas_forward(q, k, v, kv_mask, causal, scale,
                                       min(block, s), block, True,
                                       with_lse=True)
    qf, gf, lsef, delta = A._flash_bwd_prep(q, out, lse, g)
    flat = (qf, k.reshape(b * h, sk, d), v.reshape(b * h, sk, d), gf, lsef,
            delta, None if kv_mask is None else kv_mask[:, None, :], h,
            causal, scale, min(block, s), block, True)
    _, vjp = jax.vjp(lambda a, b_, c: attention_reference(
        a, b_, c, causal, scale, kv_mask=kv_mask), q, k, v)
    return flat, [r.reshape(b * h, -1, d) for r in vjp(g)]


@pytest.mark.parametrize("mask", [False, True], ids=["nomask", "kv_mask"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("s,sk,d,dtype", [
    (256, 256, 64, jnp.float32), (256, 256, 128, jnp.float32),
    (256, 256, 64, jnp.bfloat16), (256, 256, 128, jnp.bfloat16),
    (128, 384, 64, jnp.float32), (384, 256, 128, jnp.bfloat16)],
    ids=["d64-f32", "d128-f32", "d64-bf16", "d128-bf16", "s128-sk384",
         "s384-sk256"])
def test_flash_bwd_fused_bit_equal_to_split(s, sk, d, dtype, causal, mask):
    """``flash_bwd_dqkv`` makes a tile's P and dS once where ``flash_bwd_dq``
    and ``flash_bwd_dkv`` each make them: the same arithmetic in the same
    order of accumulation, so dQ, dK, dV agree to the bit (several key tiles
    and several query tiles in every case), and both match the reference's
    gradients to the tolerances the backward is held to elsewhere."""
    from sparkflow_tpu.ops import attention as A

    flat, want = _flash_bwd_case(s, sk, d, dtype, causal, mask)
    fused = A._flash_bwd_fused_flat(*flat)
    split = A._flash_bwd_split_flat(*flat)
    atol = 2e-3 if dtype == jnp.float32 else 0.15
    for f, p, w in zip(fused, split, want):
        assert f.dtype == p.dtype == dtype and f.shape == p.shape
        np.testing.assert_array_equal(np.asarray(f, np.float32),
                                      np.asarray(p, np.float32))
        np.testing.assert_allclose(np.asarray(f, np.float32),
                                   np.asarray(w, np.float32), atol=atol)


@pytest.mark.parametrize("b,h,s,d,dtype,path", [
    (4, 16, 1024, 64, jnp.bfloat16, "fused"),      # train-gpt2m's pass
    (2, 16, 4096, 128, jnp.bfloat16, "fused"),     # train-ouro-seq4k's
    (1, 2, 16384, 128, jnp.bfloat16, "fused"),     # the budget to the byte
    (1, 2, 16384, 128, jnp.float32, "split"),
    (1, 8, 32768, 64, jnp.bfloat16, "split")],
    ids=["gpt2m", "ouro-4k", "16k-bf16", "16k-f32", "32k"])
def test_flash_bwd_schedule_follows_the_heads_dq(b, h, s, d, dtype, path):
    """The backward takes the fused kernel where one head's dQ (float32
    accumulator and both buffers of its output block, lanes padded) fits
    ``_FUSED_DQ_VMEM_BUDGET`` and the dq/dkv pair past it: decided from s, d
    and the dtype alone, recorded beside the entry point's own path (which
    ``last_attention_path`` keeps reporting). Traced, not run."""
    from sparkflow_tpu.ops import attention as A

    x = jax.ShapeDtypeStruct((b, h, s, d), dtype)
    with A.record_attention_paths() as paths:
        jax.make_jaxpr(jax.grad(lambda q, k, v: A.flash_attention(
            q, k, v, causal=True, interpret=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))(x, x, x)
    assert paths == ["flash_attention:pallas", f"flash_attention_bwd:{path}"]
    assert A.last_attention_path() == "pallas"
    assert A._bwd_is_fused(s, d, dtype) == (path == "fused")


def test_ring_flash_matches_ring_and_reference(dp_mesh):
    """ring_flash_attention (pallas per-visit blocks + lse merge) must equal
    plain ring attention and the dense reference, causal and not, fwd + bwd."""
    from jax.sharding import PartitionSpec as P
    from sparkflow_tpu.ops import ring_flash_attention

    mesh = dp_mesh  # 8 devices, axis 'dp'
    rs = np.random.RandomState(0)
    B, H, S, D = 1, 2, 1024, 8  # S/8 = 128 per shard: kernel tiling holds
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D), jnp.float32)
               for _ in range(3))

    for causal in (False, True):
        def ring_fn(q, k, v):
            return ring_flash_attention(q, k, v, "dp", causal=causal)

        out = jax.shard_map(ring_fn, mesh=mesh,
                        in_specs=(P(None, None, "dp", None),) * 3,
                        out_specs=P(None, None, "dp", None),
                        check_vma=False)(q, k, v)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, err_msg=f"causal={causal}")

        # gradients flow through the custom VJP (jnp-ring recompute)
        def loss(q, k, v):
            return jax.shard_map(ring_fn, mesh=mesh,
                             in_specs=(P(None, None, "dp", None),) * 3,
                             out_specs=P(None, None, "dp", None),
                             check_vma=False)(q, k, v).sum()

        gf = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: attention_reference(
            a, b, c, causal=causal).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3, err_msg=f"causal={causal}")


def test_ring_flash_kv_mask_path(dp_mesh):
    """The mask carry (mc rotating the ring into the kernel's mask BlockSpec)
    — the genuinely new data flow — causal and not."""
    from jax.sharding import PartitionSpec as P
    from sparkflow_tpu.ops import ring_flash_attention

    rs = np.random.RandomState(4)
    B, H, S, D = 1, 2, 1024, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray((rs.rand(B, S) > 0.25).astype(np.float32))

    for causal in (False, True):
        def ring_fn(q, k, v, m):
            return ring_flash_attention(q, k, v, "dp", causal=causal,
                                        kv_mask=m)

        out = jax.shard_map(ring_fn, mesh=dp_mesh,
                        in_specs=(P(None, None, "dp", None),) * 3
                        + (P(None, "dp"),),
                        out_specs=P(None, None, "dp", None),
                        check_vma=False)(q, k, v, mask)
        ref = attention_reference(q, k, v, causal=causal, kv_mask=mask)
        # masked rows that are fully excluded under causal+mask can differ
        # in garbage content; compare only rows with any visible key
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, err_msg=f"causal={causal}")

        # gradients through the pallas ring backward with the mask rotating
        # alongside the dk/dv accumulators
        def loss(a, b_, c):
            return jax.shard_map(lambda q_, k_, v_, m_: ring_fn(q_, k_, v_, m_),
                             mesh=dp_mesh,
                             in_specs=(P(None, None, "dp", None),) * 3
                             + (P(None, "dp"),),
                             out_specs=P(None, None, "dp", None),
                             check_vma=False)(a, b_, c, mask).sum()

        gf = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b_, c: attention_reference(
            a, b_, c, causal=causal, kv_mask=mask).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-3,
                                       err_msg=f"mask grads causal={causal}")


def test_flash_block_specs_tile_legal():
    """Every pallas block mapping must satisfy the TPU tile rule: the last two
    block dims divisible by (8, 128) or equal to the array dims. The lse
    output / lse+delta operands and the kv mask used to travel as 2-D arrays
    with [1, block] blocks, which lowers fine in interpret mode but fails
    _check_block_mappings on real TPU hardware (caught live at BERT-512
    shapes). Row stats now travel as [bh, s, 1], the mask as [b, 1, sk];
    this pins the layout rule without needing a TPU."""
    from sparkflow_tpu.ops import attention as A

    def legal(block, array):
        for pos, (bdim, adim) in enumerate(zip(block[-2:], array[-2:])):
            div = (8, 128)[pos]  # sublane rule for dim -2, lane rule for -1
            if bdim != adim and bdim % div:
                return False
        return True

    bh, s, bq, bk, b, h = 6, 512, 128, 128, 2, 3
    # forward lse output layout
    assert legal((1, bq, 1), (bh, s, 1))
    # backward row-stat operands share the same layout
    spec = A._row_stat_spec(bq, "qk")
    assert spec.block_shape == (1, bq, 1)
    assert A._row_stat_spec(bq, "kq").index_map(4, 1, 2) == (4, 2, 0)
    # the kv mask travels [b, 1, sk] with [1, 1, block_k] blocks
    assert legal((1, 1, bk), (b, 1, s))
    # the old layouts are the regression: [1, block] over [bh, s] is illegal
    assert not legal((1, bq), (bh, s))


def test_flash_kv_mask_batched_rows(qkv):
    """Mask rows must be selected per batch (bh // h), exercising the 3-D
    [b, 1, sk] mask layout with b > 1 and distinct per-row masks."""
    q, k, v = qkv
    rs = np.random.RandomState(3)
    mask = jnp.asarray((rs.rand(q.shape[0], q.shape[2]) > 0.3)
                       .astype(np.float32))
    out = flash_attention(q, k, v, kv_mask=mask, interpret=True)
    ref = attention_reference(q, k, v, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    g = jax.grad(lambda a: flash_attention(a, k, v, kv_mask=mask,
                                           interpret=True).sum())(q)
    gr = jax.grad(lambda a: attention_reference(a, k, v, kv_mask=mask)
                  .sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=3e-4)


def test_auto_block_selection():
    """Auto block choice: per-dimension, per-path, short-seq clamp."""
    from sparkflow_tpu.ops.attention import _auto_block

    assert _auto_block(4096, 1024) == 1024
    assert _auto_block(4096, 512) == 512
    assert _auto_block(384, 1024) == 128   # 384 = 3*128
    assert _auto_block(64, 1024) == 64     # short seq: the old min(128, s)
    assert _auto_block(320, 1024) == 128   # 320 % 128 != 0 -> kernel falls back


def test_flash_explicit_oversized_blocks_clamp_backward():
    """Explicit block_q/block_k larger than the sequence must clamp on the
    BACKWARD path too: an unclamped 512 at seq 256 makes the dq/dkv grids
    ``s // bwd_block == 0`` and the gradients come back unwritten."""
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(1, 2, 256, 32), jnp.float32)
    k = jnp.asarray(rs.randn(1, 2, 256, 32), jnp.float32)
    v = jnp.asarray(rs.randn(1, 2, 256, 32), jnp.float32)

    def loss(a, b_, c):
        return flash_attention(a, b_, c, block_q=512, block_k=512,
                               interpret=True).sum()

    gf = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b_, c: attention_reference(a, b_, c).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-4)


def test_flash_short_query_cross_attention_keeps_kernel():
    """s=64 queries against sk=256 keys still runs the (interpret) pallas
    kernel via the short-seq clamp, matching the reference numerics."""
    import jax.numpy as jnp

    from sparkflow_tpu.ops import attention_reference, flash_attention

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 2, 64, 32), jnp.float32)
    kv = jnp.asarray(rs.randn(1, 2, 256, 32), jnp.float32)
    out = flash_attention(q, kv, kv, causal=False, interpret=True)
    ref = attention_reference(q, kv, kv, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_sharded_jit_attention_runs_pallas_per_shard(sharded_attn_mesh):
    """Sharded-jit traces no longer forfeit the flash kernel: under
    sharded_attention(mesh) the kernel runs per (batch x heads) shard via a
    nested shard_map, numerics identical to the blockwise path it replaces;
    shapes that don't divide the mesh fall back to blockwise."""
    import jax.numpy as jnp
    from sparkflow_tpu.ops import attention as A

    mesh = sharded_attn_mesh
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(4, 8, 128, 16), jnp.float32)  # b%2, h%4 divide

    with A.sharded_attention(mesh):
        out = jax.jit(lambda q: A.flash_attention(q, q, q, causal=True))(q)
    assert A.last_attention_path() == "pallas"
    ref = A.attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # gradients flow through the nested shard_map + custom vjp
    with A.sharded_attention(mesh):
        g = jax.jit(jax.grad(lambda q: A.flash_attention(
            q, q, q, causal=True).sum()))(q)
    gref = jax.grad(lambda q: A.attention_reference(
        q, q, q, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                               rtol=2e-4, atol=2e-4)

    # heads (3) don't divide tp=4 -> blockwise fallback, not a raw custom
    # call GSPMD can't partition
    qo = jnp.asarray(rs.randn(4, 3, 128, 16), jnp.float32)
    with A.sharded_attention(mesh):
        out2 = jax.jit(lambda q: A.flash_attention(q, q, q))(qo)
    assert A.last_attention_path() == "blockwise"
    np.testing.assert_allclose(np.asarray(out2),
                               np.asarray(A.attention_reference(qo, qo, qo)),
                               rtol=2e-5, atol=2e-5)


def test_sharded_jit_attention_with_kv_mask(sharded_attn_mesh):
    """The key-padding mask shards over the batch axis with q/k/v: masked
    sharded-jit attention (the BERT attention_mask path on a mesh) runs the
    pallas kernel per shard — forward AND backward — and matches the
    reference."""
    import jax.numpy as jnp
    from sparkflow_tpu.ops import attention as A

    mesh = sharded_attn_mesh
    rs = np.random.RandomState(4)
    q = jnp.asarray(rs.randn(4, 8, 128, 16), jnp.float32)
    mask = jnp.asarray((rs.rand(4, 128) > 0.3).astype(np.float32))

    with A.sharded_attention(mesh):
        out = jax.jit(lambda q, m: A.flash_attention(q, q, q, kv_mask=m))(
            q, mask)
    # the masked wrap must keep the kernel, not silently fall to blockwise
    # (which also honors the mask and would match numerically)
    assert A.last_attention_path() == "pallas"
    ref = A.attention_reference(q, q, q, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # masked custom-vjp under shard_map (the has_mask backward kernels with
    # sharded operands) — only tested unsharded elsewhere
    with A.sharded_attention(mesh):
        g = jax.jit(jax.grad(lambda q: A.flash_attention(
            q, q, q, kv_mask=mask).sum()))(q)
    gref = jax.grad(lambda q: A.attention_reference(
        q, q, q, kv_mask=mask).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                               rtol=2e-4, atol=2e-4)


def test_reference_fallback_is_loud_on_tpu_only(monkeypatch, caplog):
    """On a TPU backend a kernel entry point that takes the reference path
    says so once, with the shape and the rule; off the TPU it stays quiet.
    (The backend is steered here, in the test; nothing runs compiled.)"""
    import logging
    from sparkflow_tpu.ops import attention as A

    page, h, d = 256, 64, 128            # 8x PAGED_BLOCK_LIMIT
    q = jnp.zeros((1, h, d), jnp.float32)
    pool = jnp.zeros((2, page, h, d), jnp.float32)
    table = jnp.zeros((1, 1), jnp.int32)
    lens = jnp.ones((1,), jnp.int32)

    def call():
        return A.paged_attention(q, pool, pool, table, lens, interpret=False)

    monkeypatch.setattr(A, "_WARNED", set())
    with caplog.at_level(logging.WARNING, logger=A.logger.name):
        with A.record_attention_paths() as paths:
            call()                        # CPU backend: quiet
        assert paths == ["paged_attention:reference"]
        assert not caplog.records
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        call()
        call()                            # once per (kernel, shape, rule)
    assert len(caplog.records) == 1
    msg = caplog.records[0].getMessage()
    assert "paged_attention" in msg and "(1, 64, 128)" in msg
    assert "PAGED_BLOCK_LIMIT" in msg
    # an explicit force_xla_attention() is a request, not a fallback
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger=A.logger.name):
        with A.force_xla_attention():
            A.paged_attention(q[:, :4, :8], pool[:, :8, :4, :8], pool[:, :8, :4, :8],
                              table, lens)
    assert A.last_attention_path() == "reference" and not caplog.records
