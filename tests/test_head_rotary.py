"""``models.lm_ops.heads``: a projection's output to the attention kernels'
layout in one pass (``ops/head_rotary.py``: per-head RMSNorm, rotary
positions, ``[B, S, H * D]`` -> ``[B, H, S, D]``), held to what it replaced,
``transpose(rope(rms_norm(...)))`` of the same file, in values and in the
gradients to the input and the scale; and the three decoder families' blocks
take it. The kernels run interpreted here; ``tests/test_tpu_compile.py``
compiles them for the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkflow_tpu.models import build_registry_spec, lm_ops, model_from_json
from sparkflow_tpu.ops import head_rotary as hr
from sparkflow_tpu.ops.attention import record_attention_paths

EPS, THETA = 1e-6, 1e4


def reference(x, n, scale, positions):
    b, s, hd = x.shape
    y = x.reshape(b, s, n, hd // n)
    if scale is not None:
        y = lm_ops.rms_norm(y, scale, EPS)
    return jnp.transpose(lm_ops.rope(y, THETA, positions), (0, 2, 1, 3))


def close(got, want, dtype):
    """Equal to float32's rounding, or to two of bfloat16's roundings of the
    largest value (the reference rounds the normed heads to ``x``'s type
    before it rotates them; the kernel stays in float32 between the two)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max())


# the cells' heads at their own width, one lane tile: keye's and sdar's 32
# query heads (four grid steps of eight heads) and 4 key heads (over two
# blocks of positions), ouro's 16; the families' toy blocks below have heads
# of 8 and 16
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("rows", [1, 2])
@pytest.mark.parametrize("n, s", [(32, 64), (4, 256), (16, 64)],
                         ids=["32heads", "4heads", "16heads"])
@pytest.mark.parametrize("doubled", [True, False],
                         ids=["doubled_row", "own_index"])
@pytest.mark.parametrize("norm", [True, False], ids=["norm", "no_norm"])
def test_heads_is_rope_of_rms_norm_transposed(norm, doubled, n, s, rows,
                                              dtype):
    d = 128
    keys = jax.random.split(jax.random.PRNGKey(n + rows), 3)
    x = jax.random.normal(keys[0], (rows, s, n * d), dtype)
    scale = 1 + 0.2 * jax.random.normal(keys[1], (d,)) if norm else None
    weight = jax.random.normal(keys[2], (rows, n, s, d))
    # sdar's row: a clean and a noised copy that share their positions
    positions = jnp.arange(s) % (s // 2) if doubled else None

    def value_and_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda x, scale: jnp.sum(fn(x, scale).astype(jnp.float32)
                                     * weight),
            argnums=(0, 1) if norm else 0))(x, scale)

    with record_attention_paths() as paths:
        out = lm_ops.heads(x, n, scale, EPS, THETA, positions)
        _, grads = value_and_grads(
            lambda x, scale: lm_ops.heads(x, n, scale, EPS, THETA, positions))
    assert paths == ["head_rotary:pallas"] * 2
    assert out.shape == (rows, n, s, d) and out.dtype == dtype
    close(out, reference(x, n, scale, positions), dtype)
    _, want = value_and_grads(lambda x, scale: reference(x, n, scale,
                                                        positions))
    if norm:
        assert grads[1].shape == (d,) and grads[1].dtype == scale.dtype
        close(grads[1], want[1], dtype)
        grads, want = grads[0], want[0]
    assert grads.shape == x.shape and grads.dtype == dtype
    close(grads, want, dtype)


def test_a_head_that_cannot_be_rotated_raises():
    x = jnp.ones((1, 8, 30))
    table = jnp.ones((8, 15))
    with pytest.raises(ValueError, match="head_rotary"):       # 2 x 15: odd
        hr.head_rotary(x, 2, table, table)
    with pytest.raises(ValueError, match="head_rotary"):       # 4 heads of ?
        hr.head_rotary(x, 4, table, table)


def test_on_a_tpu_a_head_is_whole_lane_tiles_or_the_call_raises(monkeypatch):
    """No ``jnp`` path behind the kernel: a width the chip's kernel cannot
    take is an error there, before anything is traced."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    table = jnp.ones((8, 64))
    with pytest.raises(ValueError, match="multiple of 128"):
        hr.head_rotary(jnp.ones((1, 8, 128)), 2, table, table)


@pytest.mark.parametrize("h, d, group", [(32, 128, 8), (4, 128, 4),
                                         (16, 128, 8), (3, 128, 3),
                                         (7, 256, 1), (2, 2048, 1)])
def test_a_grid_step_takes_whole_heads_within_the_width(h, d, group):
    assert hr._group(h, d) == group


MOE = dict(hidden=32, num_layers=1, num_heads=4, num_kv_heads=2, head_dim=8,
           num_experts=8, experts_per_token=2, expert_dim=16,
           experts_held=[0, 4], rope_theta=1e4)
FAMILIES = {
    "sparse_moe_lm": dict(MOE, vocab_size=48, indexer_heads=2, indexer_dim=8,
                          indexer_topk=8, indexer_block=16, max_len=32),
    "block_diffusion_lm": dict(MOE, vocab_size=96, vocab_held=[0, 48],
                               mask_token_id=90, block_length=4, max_len=64),
    "looped_lm": dict(vocab_size=96, hidden=32, num_layers=1, num_heads=2,
                      head_dim=16, mlp_dim=64, passes=2, rope_theta=1e4,
                      max_len=64, head_block=64),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_familys_block_makes_q_and_k_through_the_kernel(family):
    """One block of each family's toy model: ``q`` and ``k`` each log
    ``head_rotary:pallas``, and nothing of the block logs it a third time."""
    model = model_from_json(build_registry_spec(family, **FAMILIES[family]))
    bp = model.init(jax.random.PRNGKey(0))["block_0"]
    s = FAMILIES[family]["max_len"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, s, 32))
    with record_attention_paths() as paths:
        jax.eval_shape(model._block, bp, x)
    assert paths.count("head_rotary:pallas") == 2, paths


def test_the_noised_copys_positions_reach_the_kernel():
    """``block_diffusion_lm`` feeds a clean and a noised copy of a row that
    share their positions: equal inputs at indices ``i`` and ``L + i`` give
    equal ``q`` and ``k`` there, which a rotation by the index would not."""
    model = model_from_json(build_registry_spec(
        "block_diffusion_lm", **FAMILIES["block_diffusion_lm"]))
    bp = model.init(jax.random.PRNGKey(0))["block_0"]
    half = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32))
    y = jnp.concatenate([half, half], axis=1)
    q, k, _ = model._qkv(bp, y)
    for a in (q, k):
        np.testing.assert_array_equal(a[:, :, :32], a[:, :, 32:])
        assert not np.allclose(a[:, :, 1:32], a[:, :, :31])
    by_index = lm_ops.heads(lm_ops.dense(y, bp["q_kernel"]), 4, bp["q_norm"],
                            model.rms_eps, model.rope_theta)
    np.testing.assert_array_equal(q[:, :, :32], by_index[:, :, :32])
    assert not np.allclose(q[:, :, 32:], by_index[:, :, 32:])
