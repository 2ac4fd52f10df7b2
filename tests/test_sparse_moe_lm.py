"""``sparse_moe_lm`` (RMSNorm, rotary, grouped query heads, a learned top-k
key selection, dropless SiLU-gated experts of which a share is held) against
its plain reference, ``chipbench/configs/keye_vl2_reference.py``, at toy
sizes with seeded weights, and the shares add up. ``test_sparse_ops.py``
holds the new ``ops/`` functions against the ``jnp`` references beside
them."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkflow_tpu.models import build_registry_spec, model_from_json
from sparkflow_tpu.ops import attention as A
from sparkflow_tpu.ops import grouped_matmul as gm
from sparkflow_tpu.ops import sparse_attention as sa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S, VOCAB, TOPK = 32, 48, 8


def _reference():
    path = os.path.join(ROOT, "chipbench", "configs", "keye_vl2_reference.py")
    spec = importlib.util.spec_from_file_location("keye_vl2_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def toy_cfg(held=(0, 4), experts=8, vocab=VOCAB, topk=TOPK, layers=2):
    return dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
                head_dim=8, num_hidden_layers=layers, vocab_size=vocab,
                num_local_experts=experts, num_experts=held[1] - held[0],
                experts_held_start=held[0], num_experts_per_tok=2,
                moe_intermediate_size=16, rms_norm_eps=1e-6, rope_theta=1e4,
                sa_config=dict(indexer_num_heads=2, indexer_head_dim=8,
                               topk=topk),
                norm_topk_prob=True, initializer_range=0.2)


def toy_model(cfg, **over):
    z = ref.sizes(cfg)
    kw = dict(vocab_size=z["vocab"], hidden=z["h"], num_layers=z["layers"],
              num_heads=z["nq"], num_kv_heads=z["nkv"], head_dim=z["d"],
              num_experts=z["e_all"], experts_per_token=z["per_tok"],
              expert_dim=z["m"],
              experts_held=[z["e_start"], z["e_start"] + z["e_held"]],
              indexer_heads=z["ni"], indexer_dim=z["di"],
              indexer_topk=z["topk"], indexer_block=16,
              rope_theta=z["theta"], max_len=S)
    kw.update(over)
    return model_from_json(build_registry_spec("sparse_moe_lm", **kw))


def ids_for(seed, rows=2, vocab=VOCAB):
    return np.random.default_rng(seed).integers(0, vocab, (rows, S)).astype(
        np.int32)


@pytest.fixture(scope="module")
def both():
    """Loss and gradients of the model and of the reference, once."""
    cfg = toy_cfg()
    params, ids = ref.init_params(cfg, 3), ids_for(0)
    model = toy_model(cfg)
    with jax.default_matmul_precision("highest"):
        g_ref = jax.grad(lambda p: ref.loss(p, jnp.asarray(ids), cfg))(params)
        g_model = jax.grad(lambda p: jnp.mean(model.loss_vector(
            p, {"input_ids": ids})))(params)
    return cfg, params, ids, model, g_ref, g_model


# -- the registered model against the reference -------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_logits_and_loss_match_the_reference(seed):
    cfg = toy_cfg()
    params, ids = ref.init_params(cfg, seed), ids_for(seed)
    model = toy_model(cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, jnp.asarray(ids), cfg)
        got = model.apply(params, {"input_ids": ids.astype(np.float32)},
                          ["logits"])["logits"]
        want_loss, parts = ref.row_losses(params, jnp.asarray(ids), cfg)
        got_loss, metrics = model.loss_and_metrics(params, {"input_ids": ids})
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    assert float(jnp.min(parts["indexer"])) > 0     # the loss has all parts
    assert metrics["expert_load"].shape == (2, 4)
    assert int(metrics["pairs_routed"]) == 2 * S * 2
    # of each layer's buffers (2 rows, the worst routing's size) the rows of
    # the tiles in use: at this size one tile an expert
    assert int(metrics["expert_rows_bound"]) == 2 * gm.rows_bound(S, 2, 4)
    np.testing.assert_array_equal(metrics["expert_rows_live"],
                                  [2 * 4 * gm.TILE] * 2)


LEAVES = [f"block_0/{n}" for n in ref.param_shapes(toy_cfg())["block_0"]] + [
    "block_1/experts_w1", "block_1/idx_q_kernel", "embed/tok",
    "final_ln/scale", "lm_head/kernel"]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_matches_the_reference(both, leaf):
    *_, g_ref, g_model = both
    group, name = leaf.split("/")
    want, got = g_ref[group][name], g_model[group][name]
    assert float(jnp.max(jnp.abs(want))) > 1e-4           # a live gradient
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-4)


@pytest.mark.parametrize("seq", [8, 16])
def test_with_few_keys_the_selection_is_all_of_causal_attention(seq):
    """``S <= topk``: every query selects every key at or before it, and the
    selected-key attention equals causal attention over all keys."""
    r = np.random.default_rng(seq)
    q = jnp.asarray(r.normal(size=(2, 4, seq, 8)), jnp.float32)
    k = jnp.asarray(r.normal(size=(2, 2, seq, 8)), jnp.float32)
    v = jnp.asarray(r.normal(size=(2, 2, seq, 8)), jnp.float32)
    qi = jnp.asarray(r.normal(size=(2, seq, 2, 8)), jnp.float32)
    ki = jnp.asarray(r.normal(size=(2, seq, 8)), jnp.float32)
    w = jnp.asarray(r.normal(size=(2, seq, 2)), jnp.float32)
    mask = sa.index_select(qi, ki, w, topk=16, block=8)
    np.testing.assert_array_equal(
        mask, np.broadcast_to(np.tril(np.ones((seq, seq), np.int8)),
                              mask.shape))
    out, _ = sa.selected_attention(q, k, v, mask)
    want = A.attention_reference(q, jnp.repeat(k, 2, axis=1),
                                 jnp.repeat(v, 2, axis=1), causal=True)
    np.testing.assert_allclose(out, want, atol=2e-6)


INDEXER = ("idx_q_kernel", "idx_k_kernel", "idx_w_kernel")


@pytest.mark.parametrize("part", ["indexer_loss", "cross_entropy"])
def test_the_indexers_loss_moves_the_indexer_and_nothing_else(both, part):
    """The indexer's loss has a gradient in ``W_qI``, ``W_kI``, ``W_w`` only;
    the cross-entropy (and the balance loss) has none there."""
    cfg, params, ids, *_ = both
    weights = dict(indexer_loss=(1.0, 0.0), cross_entropy=(0.0, 1.0))[part]
    model = toy_model(cfg, indexer_loss_weight=weights[0])

    def loss(p):
        lv, _ = model.loss_and_metrics(p, {"input_ids": ids})
        if part == "cross_entropy":
            return jnp.mean(lv)
        return jnp.mean(lv) - jnp.mean(toy_model(
            cfg, indexer_loss_weight=0.0).loss_vector(p, {"input_ids": ids}))

    grads = jax.grad(loss)(params)
    for group, leaves in grads.items():
        for name, g in leaves.items():
            live = float(jnp.max(jnp.abs(g))) > 1e-7
            if part == "indexer_loss":
                assert live == (name in INDEXER), (group, name)
            elif name in INDEXER:
                assert not live, (group, name)


# -- what a block's backward keeps and what it makes again ---------------------


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


@pytest.fixture(scope="module")
def kernels_of_a_gradient():
    """The kernels in the gradient of the loss with ``remat=True``, a
    layer."""
    cfg = toy_cfg()
    params, ids = ref.init_params(cfg, 3), ids_for(0)
    model = toy_model(cfg, remat=True)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.mean(
        model.loss_and_metrics(p, {"input_ids": ids})[0])))(params)
    return {name: n / cfg["num_hidden_layers"]
            for name, n in _kernel_calls(jaxpr.jaxpr, {}).items()}


# the target of the indexer's loss is made again, and is meant to be
PASSES_A_LAYER = dict(
    index_select=1, sparse_attn_fwd=1, index_kl_fwd=1, sparse_attn_probs=2,
    sparse_attn_bwd_dqkv=1, index_kl_bwd_dq=1, index_kl_bwd_dk=1,
    expert_gmm=9, expert_tgmm=3,
    # q and k, forward and made again; their transposes once
    head_rotary_fwd=4, head_rotary_bwd=2)


@pytest.mark.parametrize("kernel", sorted(PASSES_A_LAYER))
def test_a_blocks_backward_runs_each_attention_kernel_once(
        kernels_of_a_gradient, kernel):
    """The checkpoint around a block keeps the selection (as bits), the
    attention's output and logsumexp and the KL kernel's row statistics: the
    backward pass runs ``index_select``, ``sparse_attn_fwd`` and
    ``index_kl_fwd`` no second time (with no names kept each ran twice a
    layer), and the attention's backward is the one kernel
    ``sparse_attn_bwd_dqkv``. The experts' forward is still made again
    (``expert_gmm``: three products forward, again, and three backward beside
    ``expert_tgmm``'s three)."""
    assert kernels_of_a_gradient[kernel] == PASSES_A_LAYER[kernel]


@pytest.mark.parametrize("kernel", ["sparse_attn_bwd_dq",
                                    "sparse_attn_bwd_dkv"])
def test_the_pair_is_in_no_blocks_gradient_where_the_row_fits(
        kernels_of_a_gradient, kernel):
    """A row whose dK and dV fit the fused backward's budget (a toy row, and
    the cell's 8192 positions of 128 in bfloat16 at half of it) runs neither
    kernel of the pair."""
    assert kernel not in kernels_of_a_gradient
    assert sa._bwd_is_fused(8192, 128, jnp.bfloat16)


@pytest.fixture(scope="module")
def a_blocks_gradients():
    """The gradients of one checkpointed block (``jax.checkpoint`` with the
    family's ``KEPT``) in its weights and its input, with the attention's
    backward as one kernel and as the pair."""
    cfg = toy_cfg()
    model = toy_model(cfg, remat=True)
    bp = ref.init_params(cfg, 3)["block_0"]
    r = np.random.default_rng(4)
    x = jnp.asarray(r.normal(size=(2, S, 32)), jnp.float32)
    tilt = jnp.asarray(r.normal(size=x.shape), jnp.float32)

    def grad():
        # a checkpoint of its own: one is traced once for given shapes
        block = jax.checkpoint(model._block, policy=model.KEPT)

        def loss(bp, x):
            out, aux = block(bp, x)
            return jnp.sum(out * tilt) + jnp.sum(aux["indexer"])

        return jax.grad(loss, argnums=(0, 1))(bp, x)

    with pytest.MonkeyPatch.context() as patch, \
            A.record_attention_paths() as paths:
        fused = grad()
        patch.setattr(sa, "_FUSED_DKV_VMEM_BUDGET", 0)
        pair = grad()
    # (the block's ``head_rotary:pallas`` are in the log too)
    assert [p for p in paths if p.startswith("sparse_attention_bwd")] == [
        "sparse_attention_bwd:fused", "sparse_attention_bwd:split"]
    return dict(fused[0], x=fused[1]), dict(pair[0], x=pair[1])


@pytest.mark.parametrize("leaf", ["x"] + list(ref.param_shapes(toy_cfg())[
    "block_0"]))
def test_a_checkpointed_blocks_gradient_is_the_pairs_to_the_bit(
        a_blocks_gradients, leaf):
    fused, pair = a_blocks_gradients
    assert float(jnp.max(jnp.abs(pair[leaf]))) > 1e-7
    np.testing.assert_array_equal(fused[leaf], pair[leaf])


@pytest.fixture(scope="module")
def kept_and_remade():
    """Loss and gradients with ``remat=True`` and with ``remat=False``."""
    cfg = toy_cfg()
    params, ids = ref.init_params(cfg, 3), ids_for(0)
    out = {}
    with jax.default_matmul_precision("highest"):
        for remat in (True, False):
            model = toy_model(cfg, remat=remat)
            out[remat] = jax.value_and_grad(lambda p: jnp.mean(
                model.loss_vector(p, {"input_ids": ids})))(params)
    return out


@pytest.mark.parametrize("leaf", ["loss"] + LEAVES)
def test_keeping_by_name_changes_no_leafs_gradient(kept_and_remade, leaf):
    """What the checkpoint keeps is what it would make again, to the bit: the
    loss is equal with and without it, and so are the gradients but for
    three norm scales, which XLA reduces in another order inside the
    checkpoint (1e-8 apart, CPU): every leaf within the tolerance of the
    test against the reference."""
    (loss, grads), (want_loss, want) = (kept_and_remade[True],
                                        kept_and_remade[False])
    if leaf == "loss":
        np.testing.assert_array_equal(loss, want_loss)
        return
    group, name = leaf.split("/")
    assert float(jnp.max(jnp.abs(want[group][name]))) > 1e-4
    np.testing.assert_allclose(grads[group][name], want[group][name],
                               atol=2e-6, rtol=1e-4)


# -- the shares add up --------------------------------------------------------


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_expert_outputs_sum_to_the_uncut_layers(shares):
    cfg = toy_cfg(held=(0, 8))
    whole = toy_model(cfg)
    bp = ref.init_params(cfg, 7)["block_0"]
    y = jnp.asarray(np.random.default_rng(1).normal(size=(2, S, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, balance, load = whole._experts(bp, y)
        per = 8 // shares
        total, loads = 0.0, []
        for i in range(shares):
            lo, hi = i * per, (i + 1) * per
            part = toy_model(toy_cfg(held=(lo, hi)))
            cut = dict(bp, **{k: bp[k][lo:hi] for k in
                              ("experts_w1", "experts_w3", "experts_w2")})
            out, b, l = part._experts(cut, y)
            np.testing.assert_allclose(b, balance, rtol=1e-6)
            total, loads = total + out, loads + [l]
        ref_out = ref.experts(y[0], bp, cfg, jnp.matmul)[0]
    np.testing.assert_allclose(total, want, atol=2e-6)
    np.testing.assert_allclose(want[0], ref_out, atol=2e-6)
    np.testing.assert_array_equal(np.concatenate(loads), load)
    assert int(jnp.sum(load)) == 2 * S * 2          # every pair somewhere


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_vocabulary_slices_logits_concatenate_to_the_whole_heads(shares):
    cfg = toy_cfg()
    params = ref.init_params(cfg, 9)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, S, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = toy_model(cfg)._head(params, x)
        per = VOCAB // shares
        parts = []
        for i in range(shares):
            lo, hi = i * per, (i + 1) * per
            part = toy_model(cfg, vocab_held=[lo, hi])
            assert part.param_specs()["embed"]["tok"][0] == (per, 32)
            cut = dict(params, lm_head={
                "kernel": params["lm_head"]["kernel"][:, lo:hi]})
            parts.append(part._head(cut, x))
    np.testing.assert_allclose(jnp.concatenate(parts, axis=-1), want,
                               atol=1e-6)


def test_a_slice_of_the_vocabulary_takes_its_own_ids():
    cfg = toy_cfg(vocab=16)
    part = toy_model(cfg, vocab_size=VOCAB, vocab_held=[16, 32])
    params = ref.init_params(cfg, 4)
    ids = ids_for(5, vocab=16)
    with jax.default_matmul_precision("highest"):
        want = ref.row_losses(params, jnp.asarray(ids), cfg)[0]
        got = part.loss_vector(params, {"input_ids": ids + 16})
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("expert", [0, 3])
def test_no_token_is_dropped_when_the_router_is_forced_onto_one_expert(expert):
    cfg = toy_cfg()
    model = toy_model(cfg)
    bp = dict(ref.init_params(cfg, 11)["block_0"])
    bp["router"] = jnp.zeros_like(bp["router"]).at[:, expert].set(50.0)
    y = jnp.abs(jnp.asarray(np.random.default_rng(3).normal(size=(2, S, 32)),
                            jnp.float32))
    with jax.default_matmul_precision("highest"):
        out, _, load = model._experts(bp, y)
        want = jnp.stack([ref.experts(row, bp, cfg, jnp.matmul)[0]
                          for row in y])
    assert int(load[expert]) == 2 * S              # every token, none dropped
    np.testing.assert_allclose(out, want, atol=2e-6)
    assert float(jnp.min(jnp.max(jnp.abs(out), axis=-1))) > 0


# -- through the normal path ---------------------------------------------------


def test_trainer_fits_it_on_the_fused_path_and_returns_its_counters():
    from sparkflow_tpu.trainer import Trainer

    cfg = toy_cfg()
    z = ref.sizes(cfg)
    spec = build_registry_spec(
        "sparse_moe_lm", vocab_size=VOCAB, hidden=32, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, num_experts=8,
        experts_per_token=2, expert_dim=16, experts_held=[0, 4],
        indexer_heads=2, indexer_dim=8, indexer_topk=TOPK, indexer_block=16,
        rope_theta=1e4, max_len=S)
    trainer = Trainer(spec, "input_ids", None, optimizer="adam",
                      learning_rate=3e-3, mini_batch_size=2, iters=2,
                      shuffle_per_iter=False, debug_recompiles=True, seed=1)
    rows = ids_for(1, rows=8).astype(np.float32)
    first = trainer.fit(rows, init_params=ref.init_params(cfg, 1))
    again = trainer.fit(rows, init_params=trainer.params)
    assert again.losses[-1] < first.losses[0]
    assert first.metrics["expert_load"].shape == (2, 4, 2, z["e_held"])
    assert first.metrics["selected_keys"].shape == (2, 4, 2)
    assert (first.metrics["pairs_routed"] == 2 * S * 2).all()
    assert first.metrics["expert_rows_live"].shape == (2, 4, 2)
    assert (first.metrics["expert_rows_live"].max()
            <= first.metrics["expert_rows_bound"].min())
    assert "no traced builds" in trainer.recompile_report


def test_a_model_without_counters_keeps_its_plain_result():
    from sparkflow_tpu.trainer import Trainer

    spec = build_registry_spec("transformer_lm", vocab_size=VOCAB, hidden=16,
                               num_layers=1, num_heads=2, mlp_dim=32,
                               max_len=S, dropout=0.0)
    trainer = Trainer(spec, "input_ids", None, mini_batch_size=2, iters=2,
                      shuffle_per_iter=False, seed=1)
    assert trainer.fit(ids_for(2, rows=4).astype(np.float32)).metrics is None


def test_the_decode_plane_refuses_it_and_says_why():
    from sparkflow_tpu.serving.decode import DecodeEngine

    with pytest.raises(TypeError, match="trains only.*indexer"):
        DecodeEngine(toy_model(toy_cfg()), None)
