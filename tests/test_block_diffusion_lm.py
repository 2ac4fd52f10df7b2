"""``block_diffusion_lm`` (the decoder of ``sparse_moe_lm`` under the
block-diffusion mask and a masked-token loss) against its plain reference,
``chipbench/configs/sdar_reference.py``, at toy sizes with seeded weights;
the mask by its meaning; the noise helper's law; the shares add up; through
``Trainer.fit``. ``test_block_attention.py`` holds the kernels against the
``jnp`` reference beside them."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkflow_tpu.models import (build_registry_spec, model_from_json,
                                  noise_rows)
from sparkflow_tpu.models.sparse_moe_lm import MoEDecoder, SparseMoELM, rope
from sparkflow_tpu.ops import attention as A
from sparkflow_tpu.ops import block_attention as ba
from sparkflow_tpu.ops import grouped_matmul as gm
from sparkflow_tpu.ops import sparse_attention as sa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, B, VOCAB, ALL, MASK = 32, 4, 48, 96, 90


def _reference():
    path = os.path.join(ROOT, "chipbench", "configs", "sdar_reference.py")
    spec = importlib.util.spec_from_file_location("sdar_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def toy_cfg(held=(0, 4), experts=8, vocab=VOCAB, layers=2, block=B):
    return dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
                head_dim=8, num_hidden_layers=layers, vocab_size=vocab,
                published_num_experts=experts, num_experts=held[1] - held[0],
                experts_held_start=held[0], num_experts_per_tok=2,
                moe_intermediate_size=16, rms_norm_eps=1e-6, rope_theta=1e4,
                norm_topk_prob=True, initializer_range=0.2,
                mask_token_id=MASK, block_length=block)


def toy_model(cfg, **over):
    z = ref.sizes(cfg)
    kw = dict(vocab_size=ALL, vocab_held=[0, z["vocab"]],
              mask_token_id=z["mask_id"], block_length=z["block"],
              hidden=z["h"], num_layers=z["layers"], num_heads=z["nq"],
              num_kv_heads=z["nkv"], head_dim=z["d"], num_experts=z["e_all"],
              experts_per_token=z["per_tok"], expert_dim=z["m"],
              experts_held=[z["e_start"], z["e_start"] + z["e_held"]],
              rope_theta=z["theta"], max_len=2 * L)
    kw.update(over)
    return model_from_json(build_registry_spec("block_diffusion_lm", **kw))


def rows_for(seed, rows=2, vocab=VOCAB, block=B):
    ids = np.random.default_rng(seed).integers(0, vocab, (rows, L)).astype(
        np.int32)
    return noise_rows(ids, block, MASK, seed)


@pytest.fixture(scope="module")
def both():
    """Gradients of the model's and of the reference's loss, once."""
    cfg = toy_cfg()
    params, rows = ref.init_params(cfg, 3), rows_for(0)
    model = toy_model(cfg)
    with jax.default_matmul_precision("highest"):
        g_ref = jax.grad(lambda p: ref.loss(p, jnp.asarray(rows), cfg))(params)
        g_model = jax.grad(lambda p: jnp.mean(model.loss_vector(
            p, {"input_ids": rows})))(params)
    return cfg, params, rows, model, g_ref, g_model


# -- the registered model against the reference -------------------------------


# float32 at the highest matmul precision on both sides: what is left is the
# order of the sums (the kernel's tiles, the grouped product's rows, the
# head's stretches): 2e-5 on logits of order one, 1e-5 relative on a loss
@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_logits_and_row_losses_match_the_reference(seed):
    cfg = toy_cfg()
    params, rows = ref.init_params(cfg, seed), rows_for(seed)
    model = toy_model(cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, jnp.asarray(rows), cfg)
        got = model.apply(params, {"input_ids": rows.astype(np.float32)},
                          ["logits"])["logits"]
        want_loss, parts = ref.row_losses(params, jnp.asarray(rows), cfg)
        got_loss, metrics = model.loss_and_metrics(params,
                                                   {"input_ids": rows})
    assert got.shape == (2, L, VOCAB)               # the noised half only
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    assert float(jnp.min(parts["balance"])) > 0     # the loss has both parts
    assert metrics["expert_load"].shape == (2, 4)
    assert int(metrics["pairs_routed"]) == 2 * 2 * L * 2
    assert int(metrics["expert_rows_bound"]) == 2 * gm.rows_bound(2 * L, 2, 4)
    np.testing.assert_array_equal(metrics["expert_rows_live"],
                                  [2 * 4 * gm.TILE] * 2)
    assert int(metrics["masked_tokens"]) == int((rows[:, L:] == MASK).sum())


LEAVES = [f"block_0/{n}" for n in ref.param_shapes(toy_cfg())["block_0"]] + [
    "block_1/experts_w1", "block_1/q_kernel", "embed/tok", "final_ln/scale",
    "lm_head/kernel"]


# as above; the absolute 2e-6 is for gradients of order 0.01-0.3
@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_matches_the_reference(both, leaf):
    *_, g_ref, g_model = both
    group, name = leaf.split("/")
    want, got = g_ref[group][name], g_model[group][name]
    assert float(jnp.max(jnp.abs(want))) > 1e-4           # a live gradient
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)


def test_the_mask_tokens_embedding_row_learns_and_is_never_a_target(both):
    cfg, params, rows, model, g_ref, g_model = both
    mask_row = g_model["embed"]["tok"][VOCAB]
    assert float(jnp.max(jnp.abs(mask_row))) > 1e-4
    np.testing.assert_allclose(mask_row, g_ref["embed"]["tok"][VOCAB],
                               atol=2e-6, rtol=1e-4)
    assert g_model["lm_head"]["kernel"].shape == (32, VOCAB)


def _kernel_calls(jaxpr, counts):
    """Count the ``pallas_call``s of ``jaxpr`` by name, through every
    sub-jaxpr an equation holds (``scan``, ``checkpoint``, ``custom_vjp``)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            counts[name] = counts.get(name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)           # a ClosedJaxpr
                if hasattr(sub, "eqns"):
                    _kernel_calls(sub, counts)
    return counts


def _kernels_of_the_loss_gradient(remat):
    cfg = toy_cfg()
    params, rows = ref.init_params(cfg, 3), rows_for(0)
    model = toy_model(cfg, remat=remat)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.mean(
        model.loss_and_metrics(p, {"input_ids": rows})[0])))(params)
    return cfg, _kernel_calls(jaxpr.jaxpr, {})


@pytest.mark.parametrize("remat", [True, False])
def test_a_blocks_backward_runs_the_forward_kernel_once(remat):
    """The block's checkpoint keeps the attention's output and logsumexp by
    name: ``block_attn_fwd`` runs once a layer, not twice; the backward is
    the one kernel ``block_attn_bwd_dqkv`` and the pair is in no block's
    gradient (a row of the cell's size is on that side of the budget too)."""
    with A.record_attention_paths() as paths:
        cfg, calls = _kernels_of_the_loss_gradient(remat)
    for name in ("block_attn_fwd", "block_attn_bwd_dqkv"):
        assert calls[name] == cfg["num_hidden_layers"], calls
    assert not {"block_attn_bwd_dq", "block_attn_bwd_dkv"} & set(calls)
    # (a checkpointed block is traced once for all layers)
    assert set(paths) == {f"block_attention_fwd:{L}x{L}",
                          "block_attention_bwd:fused", "head_rotary:pallas"}


@pytest.mark.parametrize("remat", [True, False])
def test_past_the_budget_a_blocks_backward_is_the_pair_once_a_layer(
        remat, monkeypatch):
    monkeypatch.setattr(sa, "_FUSED_DKV_VMEM_BUDGET", 0)
    cfg, calls = _kernels_of_the_loss_gradient(remat)
    for name in ("block_attn_fwd", "block_attn_bwd_dq", "block_attn_bwd_dkv"):
        assert calls[name] == cfg["num_hidden_layers"], calls
    assert "block_attn_bwd_dqkv" not in calls


@pytest.fixture(scope="module")
def a_blocks_gradients():
    """The gradients of one checkpointed block (``jax.checkpoint`` with the
    family's ``KEPT``) in its weights and its input, with the backward as
    one kernel and as the pair."""
    cfg = toy_cfg()
    model = toy_model(cfg, remat=True)
    bp = ref.init_params(cfg, 3)["block_0"]
    r = np.random.default_rng(4)
    x = jnp.asarray(r.normal(size=(2, 2 * L, 32)), jnp.float32)
    tilt = jnp.asarray(r.normal(size=x.shape), jnp.float32)

    def grad():
        # a checkpoint of its own: one is traced once for given shapes
        block = jax.checkpoint(model._block, policy=model.KEPT)
        return jax.grad(lambda bp, x: jnp.sum(block(bp, x)[0] * tilt),
                        argnums=(0, 1))(bp, x)

    with pytest.MonkeyPatch.context() as patch, \
            A.record_attention_paths() as paths:
        fused = grad()
        patch.setattr(sa, "_FUSED_DKV_VMEM_BUDGET", 0)
        pair = grad()
    # (the block's ``head_rotary:pallas`` are in the log too)
    assert [p for p in paths if p.startswith("block_attention_bwd")] == [
        "block_attention_bwd:fused", "block_attention_bwd:split"]
    return dict(fused[0], x=fused[1]), dict(pair[0], x=pair[1])


@pytest.mark.parametrize("leaf", ["x"] + list(ref.param_shapes(toy_cfg())[
    "block_0"]))
def test_a_checkpointed_blocks_gradient_is_the_pairs_to_the_bit(
        a_blocks_gradients, leaf):
    fused, pair = a_blocks_gradients
    assert float(jnp.max(jnp.abs(pair[leaf]))) > 1e-5
    np.testing.assert_array_equal(fused[leaf], pair[leaf])


# -- the mask by its meaning ----------------------------------------------------


def _noised_logits(model, params, rows):
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.apply(
            params, {"input_ids": rows}, ["logits"])["logits"])[0]


def _moved(a, b):
    """Which blocks' noised logits differ at all."""
    return np.abs(a - b).reshape(L // B, -1).max(axis=-1) > 0


@pytest.mark.parametrize("blk", [0, 3, 6])
def test_a_clean_token_reaches_only_later_blocks_noised_logits(blk):
    cfg = toy_cfg()
    params, rows = ref.init_params(cfg, 5), rows_for(7, rows=1)
    model = toy_model(cfg)
    other = rows.copy()
    i = blk * B + 1
    other[0, i] = (rows[0, i] + 1) % VOCAB
    moved = _moved(_noised_logits(model, params, rows),
                   _noised_logits(model, params, other))
    assert not moved[:blk + 1].any()        # blocks <= b as they were
    assert moved[blk + 1:].any()            # some later block moved


@pytest.mark.parametrize("blk", [0, 3, 7])
def test_a_noised_token_reaches_only_its_own_blocks_noised_logits(blk):
    cfg = toy_cfg()
    params, rows = ref.init_params(cfg, 5), rows_for(7, rows=1)
    model = toy_model(cfg)
    other = rows.copy()
    i = L + blk * B + 2
    other[0, i] = MASK if rows[0, i] != MASK else rows[0, i - L]
    moved = _moved(_noised_logits(model, params, rows),
                   _noised_logits(model, params, other))
    assert moved[blk] and moved.sum() == 1


def test_both_copies_of_a_position_get_the_same_rotary_angle():
    model = toy_model(toy_cfg())
    pos = model._positions(2 * L)
    np.testing.assert_array_equal(pos[:L], pos[L:])
    np.testing.assert_array_equal(pos[:L], np.arange(L))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, L, 2, 8)),
                    jnp.float32)
    twice = rope(jnp.concatenate([x, x], axis=1), 1e4, pos)
    np.testing.assert_array_equal(twice[:, :L], twice[:, L:])
    np.testing.assert_array_equal(twice[:, :L], rope(x, 1e4))


# -- the noise helper -----------------------------------------------------------


@pytest.mark.parametrize("block", [2, 4, 8])
def test_the_noise_helper_masks_one_to_b_of_every_block_uniformly(block):
    ids = np.random.default_rng(0).integers(0, VOCAB, (64, 512)).astype(
        np.int32)
    rows = noise_rows(ids, block, MASK, seed=11)
    assert rows.shape == (64, 1024) and rows.dtype == ids.dtype
    np.testing.assert_array_equal(rows[:, :512], ids)
    masked = rows[:, 512:] == MASK
    np.testing.assert_array_equal(rows[:, 512:][~masked], ids[~masked])
    k = masked.reshape(-1, block).sum(axis=-1)
    assert k.min() == 1 and k.max() == block       # every block 1..B masked
    share = np.bincount(k, minlength=block + 1)[1:] / k.size
    np.testing.assert_allclose(share, 1.0 / block, atol=0.02)   # k uniform
    # the positions uniform: each place of a block is masked equally often
    place = masked.reshape(-1, block).mean(axis=0)
    np.testing.assert_allclose(place, (block + 1) / (2 * block), atol=0.02)
    np.testing.assert_array_equal(rows, noise_rows(ids, block, MASK, seed=11))
    assert (rows != noise_rows(ids, block, MASK, seed=12)).any()


def test_the_benchmarks_generator_draws_by_the_same_law():
    from chipbench import traffic_blockdiff

    mix = dict(rows=64, seq_len=512, noise=dict(block_length=4),
               token_law=dict(law="zipf", exponent=1.0))
    masked = traffic_blockdiff.masked_positions(mix, 3000000019)
    k = masked.reshape(-1, 4).sum(axis=-1)
    assert k.min() == 1 and k.max() == 4
    np.testing.assert_allclose(np.bincount(k)[1:] / k.size, 0.25, atol=0.02)
    rows = traffic_blockdiff.noised_rows(
        mix, 3000000019, dict(block_length=4, vocab_size=VOCAB,
                              mask_token_id=MASK))
    assert rows.shape == (64, 1024) and rows.dtype == np.int32
    np.testing.assert_array_equal(rows[:, 512:] == MASK, masked)
    assert rows[:, :512].max() < VOCAB


# -- the shares add up ------------------------------------------------------------


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_expert_outputs_sum_to_the_uncut_references_layer(shares):
    cfg = toy_cfg(held=(0, 8))
    bp = ref.init_params(cfg, 7)["block_0"]
    y = jnp.asarray(np.random.default_rng(1).normal(size=(1, 2 * L, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, balance, load = ref.experts(y[0], bp, cfg, jnp.matmul)
        per = 8 // shares
        total, loads = 0.0, []
        for i in range(shares):
            lo, hi = i * per, (i + 1) * per
            part = toy_model(toy_cfg(held=(lo, hi)))
            cut = dict(bp, **{k: bp[k][lo:hi] for k in
                              ("experts_w1", "experts_w3", "experts_w2")})
            out, b, l = part._experts(cut, y)
            np.testing.assert_allclose(b[0], balance, rtol=1e-6)
            total, loads = total + out[0], loads + [l]
    np.testing.assert_allclose(total, want, atol=2e-6)
    np.testing.assert_array_equal(np.concatenate(loads), load)
    assert int(jnp.sum(load)) == 2 * L * 2          # every pair somewhere


def test_the_two_families_share_one_block():
    """The projections, norms, router, experts and head are the base's, not
    a copy: neither family overrides them."""
    from sparkflow_tpu.models.block_diffusion_lm import BlockDiffusionLM

    for cls in (SparseMoELM, BlockDiffusionLM):
        for shared in ("_qkv", "_experts", "_block", "_head", "_encode",
                       "_weighted_nll", "param_specs"):
            assert getattr(cls, shared) is getattr(MoEDecoder, shared), (
                cls.__name__, shared)
        assert cls._attend is not MoEDecoder._attend


@pytest.mark.parametrize("bad,match", [
    (dict(mask_token_id=5), "outside vocab_held"),
    (dict(max_len=2 * L + 2), "twice a whole number of blocks"),
    (dict(block_length=3, max_len=2 * 3 * 8), "a power of two")])
def test_the_constructor_refuses_what_it_cannot_train(bad, match):
    with pytest.raises(ValueError, match=match):
        toy_model(toy_cfg(), **bad)


# -- through the normal path ---------------------------------------------------


def test_trainer_fits_it_on_the_fused_path_and_returns_its_counters():
    from sparkflow_tpu.trainer import Trainer

    cfg = toy_cfg()
    spec = build_registry_spec(
        "block_diffusion_lm", vocab_size=ALL, vocab_held=[0, VOCAB],
        mask_token_id=MASK, block_length=B, hidden=32, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, num_experts=8,
        experts_per_token=2, expert_dim=16, experts_held=[0, 4],
        rope_theta=1e4, max_len=2 * L)
    trainer = Trainer(spec, "input_ids", None, optimizer="adam",
                      learning_rate=3e-3, mini_batch_size=2, iters=2,
                      shuffle_per_iter=False, debug_recompiles=True, seed=1)
    rows = rows_for(1, rows=8)
    first = trainer.fit(rows.astype(np.float32),
                        init_params=ref.init_params(cfg, 1))
    again = trainer.fit(rows.astype(np.float32), init_params=trainer.params)
    assert again.losses[-1] < first.losses[0]
    assert first.metrics["expert_load"].shape == (2, 4, 2, 4)
    assert (first.metrics["pairs_routed"] == 2 * 2 * L * 2).all()
    assert first.metrics["expert_rows_live"].shape == (2, 4, 2)
    assert (first.metrics["expert_rows_live"].max()
            <= first.metrics["expert_rows_bound"].min())
    # the positions that carry loss, step by step, as the rows hold them
    per_step = (rows[:, L:] == MASK).reshape(4, -1).sum(axis=-1)
    np.testing.assert_array_equal(first.metrics["masked_tokens"],
                                  np.stack([per_step, per_step]))
    assert "no traced builds" in trainer.recompile_report


def test_the_decode_plane_refuses_it_and_says_why():
    from sparkflow_tpu.serving.decode import DecodeEngine

    with pytest.raises(TypeError, match="trains only.*denoises a whole block"):
        DecodeEngine(toy_model(toy_cfg()), None)
