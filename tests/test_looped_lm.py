"""``looped_lm`` (one stack of sandwich-norm blocks run several times with the
same weights, a head and an exit gate after every pass, the expected loss
under the exit distribution) against its plain reference,
``chipbench/configs/ouro_reference.py``, at toy sizes with seeded weights;
the weights are shared; broken models fail the comparison; through
``Trainer.fit``; and the head and cross-entropy it shares with the MoE
families (``models/lm_ops.py``) leave their losses as they were."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkflow_tpu.models import build_registry_spec, model_from_json
from sparkflow_tpu.models.looped_lm import LoopedLM
from sparkflow_tpu.models.sparse_moe_lm import MoEDecoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# 128 positions: the least the attention's pallas kernel tiles (interpreted
# on the CPU); 64 would take the jnp path beside it
S, VOCAB, T, LAYERS = 128, 96, 3, 2


def _load(name):
    path = os.path.join(ROOT, "chipbench", "configs", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("ouro_reference")


def toy_cfg(passes=T, beta=0.1):
    return dict(hidden_size=32, num_attention_heads=2, head_dim=16,
                intermediate_size=64, vocab_size=VOCAB,
                num_hidden_layers=LAYERS, total_ut_steps=passes,
                rms_norm_eps=1e-6, rope_theta=1e4, exit_entropy_weight=beta,
                initializer_range=0.2)


def toy_kw(cfg, **over):
    z = ref.sizes(cfg)
    kw = dict(vocab_size=z["vocab"], hidden=z["h"], num_layers=z["layers"],
              num_heads=z["heads"], head_dim=z["d"], mlp_dim=z["m"],
              passes=z["passes"], rope_theta=z["theta"],
              exit_entropy_weight=z["beta"], max_len=S, head_block=64)
    kw.update(over)
    return kw


def toy_model(cfg, **over):
    return model_from_json(build_registry_spec("looped_lm",
                                               **toy_kw(cfg, **over)))


def ids_for(seed, rows=2):
    return np.random.default_rng(seed).integers(0, VOCAB, (rows, S)).astype(
        np.int32)


LEAVES = ref.leaf_names(toy_cfg())


def readings(model, cfg, params, ids):
    """What the comparison reads of a model: every pass's logits, each row's
    loss, the exit distribution's mean and every leaf's gradient."""
    with jax.default_matmul_precision("highest"):
        logits = model.apply(params, {"input_ids": ids.astype(np.float32)},
                             ["loop_logits"])["loop_logits"]
        loss, metrics = model.loss_and_metrics(params, {"input_ids": ids})
        grads = jax.grad(lambda p: jnp.mean(model.loss_vector(
            p, {"input_ids": ids})))(params)
    return dict(logits=logits, loss=loss, exit_mass=metrics["exit_mass"],
                **{leaf: grads[leaf.split("/")[0]][leaf.split("/")[1]]
                   for leaf in LEAVES})


@pytest.fixture(scope="module")
def want():
    """The reference's side of :func:`readings`, once."""
    cfg = toy_cfg()
    params, ids = ref.init_params(cfg, 3), ids_for(0)
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(params, jnp.asarray(ids), cfg)
        loss, parts = ref.row_losses(params, jnp.asarray(ids), cfg)
        grads = jax.grad(lambda p: ref.loss(p, jnp.asarray(ids), cfg))(params)
    out = dict(logits=logits, loss=loss,
               exit_mass=jnp.mean(parts["exit_mass"], axis=0),
               **{leaf: grads[leaf.split("/")[0]][leaf.split("/")[1]]
                  for leaf in LEAVES})
    return cfg, params, ids, out


# float32 at the highest matmul precision on both sides: what is left is the
# order of the sums (the kernel's tiles, the head's stretches), 1e-5 on
# logits of order one, 1e-5 relative on a loss of 5 and on a probability; on
# gradients of order 0.004-0.09 an absolute 1e-6 and 1e-4 of the value. The
# same model in bfloat16 is off by 1e-2 on the logits and fails every one of
# them (the last test of this group)
TOLERANCE = dict(logits=dict(atol=1e-5), loss=dict(rtol=1e-5),
                 exit_mass=dict(rtol=1e-5))


def failures(got, want):
    """The names of the readings that do not agree."""
    bad = []
    for name, value in want.items():
        tol = TOLERANCE.get(name, dict(atol=1e-6, rtol=1e-4))
        if (np.shape(got[name]) != np.shape(value)
                or not np.allclose(got[name], value, **tol)):
            bad.append(name)
    return bad


# -- the registered model against the reference -------------------------------


@pytest.fixture(scope="module")
def got(want):
    cfg, params, ids, _ = want
    return readings(toy_model(cfg), cfg, params, ids)


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_every_passes_logits_and_the_losses_match_the_reference(seed):
    cfg = toy_cfg()
    params, ids = ref.init_params(cfg, seed), ids_for(seed)
    model = toy_model(cfg)
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(params, jnp.asarray(ids), cfg)
        loss, parts = ref.row_losses(params, jnp.asarray(ids), cfg)
        out = model.apply(params, {"input_ids": ids.astype(np.float32)},
                          ["loop_logits", "logits", "pred"])
        got_loss, metrics = model.loss_and_metrics(params, {"input_ids": ids})
    assert out["loop_logits"].shape == (2, T, S, VOCAB)
    np.testing.assert_allclose(out["loop_logits"], logits, atol=1e-5)
    # what ``Trainer.predict_fn`` serves is the last pass's
    np.testing.assert_array_equal(out["logits"], out["loop_logits"][:, -1])
    np.testing.assert_array_equal(out["pred"],
                                  np.argmax(out["logits"], axis=-1))
    np.testing.assert_allclose(got_loss, loss, rtol=1e-5)
    np.testing.assert_allclose(metrics["exit_mass"],
                               jnp.mean(parts["exit_mass"], axis=0), rtol=1e-5)
    np.testing.assert_allclose(metrics["loop_loss"],
                               jnp.mean(parts["ce"], axis=0), rtol=1e-5)
    np.testing.assert_allclose(metrics["exit_entropy"],
                               jnp.mean(parts["entropy"]), rtol=1e-5)
    np.testing.assert_allclose(float(jnp.sum(metrics["exit_mass"])), 1.0,
                               rtol=1e-6)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_matches_the_reference(want, got, leaf):
    expected = want[3][leaf]
    assert float(jnp.max(jnp.abs(expected))) > 1e-3          # a live gradient
    assert expected.shape == got[leaf].shape
    np.testing.assert_allclose(got[leaf], expected, atol=1e-6, rtol=1e-4)


def test_the_comparison_holds_the_sound_model(want, got):
    assert failures(got, want[3]) == []


class _OnePassShort(LoopedLM):
    def __init__(self, **kw):
        super().__init__(**dict(kw, passes=kw["passes"] - 1))


class _GateDetached(LoopedLM):
    def _gate_logit(self, params, h):
        return super()._gate_logit(jax.lax.stop_gradient(params), h)


class _NoPostNorms(LoopedLM):
    def _block(self, bp, x):
        from sparkflow_tpu.models import looped_lm

        real = looped_lm.rms_norm
        looped_lm.rms_norm = lambda a, g, eps: (
            a if g is bp["ln1_post_scale"] or g is bp["ln2_post_scale"]
            else real(a, g, eps))
        try:
            return super()._block(bp, x)
        finally:
            looped_lm.rms_norm = real


@pytest.mark.parametrize("broken,must_fail", [
    (_OnePassShort, {"logits", "loss", "exit_mass"}),
    (_GateDetached, {"exit_gate/kernel", "exit_gate/bias"}),
    (_NoPostNorms, {"logits", "loss", "block_0/ln1_post_scale"})])
def test_a_broken_model_fails_the_comparison(want, broken, must_fail):
    cfg, params, ids, expected = want
    bad = set(failures(readings(broken(**toy_kw(cfg)), cfg, params, ids),
                       expected))
    assert must_fail <= bad, bad
    if broken is _GateDetached:       # everything but the gate is as it was
        assert bad == must_fail


def test_bfloat16_in_float32s_place_fails_the_comparison(want):
    cfg, params, ids, expected = want
    model = model_from_json(build_registry_spec("looped_lm", **toy_kw(cfg)),
                            compute_dtype="bfloat16")
    bad = failures(readings(model, cfg, params, ids), expected)
    assert {"logits", "loss", "lm_head/kernel", "exit_gate/kernel",
            "block_0/q_kernel"} <= set(bad), bad


# -- what the loop means -------------------------------------------------------


def _unrolled(step, carry, ts):
    """``jax.lax.scan`` as a Python loop, so that each turn can be told
    apart from outside."""
    outs = []
    for t in range(len(ts)):
        carry, out = step(carry, t)
        outs.append(out)
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *outs)


def test_a_blocks_gradient_is_the_sum_of_the_passes(want, got, monkeypatch):
    """Every pass given its own copy of the blocks: the shared weights'
    gradient is the sum of the copies'."""
    cfg, params, ids, _ = want
    model = toy_model(cfg)
    blocks = {k: v for k, v in params.items() if k.startswith("block_")}
    monkeypatch.setattr(jax.lax, "scan", _unrolled)

    def loss(copies):
        turn = iter(copies)
        monkeypatch.setattr(model, "_pass", lambda p, x: LoopedLM._pass(
            model, {**p, **next(turn)}, x), raising=False)
        return jnp.mean(model.loss_vector(params, {"input_ids": ids}))

    with jax.default_matmul_precision("highest"):
        per_pass = jax.grad(loss)([blocks] * T)
    for name in ("q_kernel", "down_kernel", "ln2_post_scale"):
        parts = [g["block_0"][name] for g in per_pass]
        assert all(float(jnp.max(jnp.abs(p))) > 1e-4 for p in parts)
        np.testing.assert_allclose(sum(parts), got[f"block_0/{name}"],
                                   atol=1e-6, rtol=1e-4)


def test_one_pass_and_no_entropy_weight_is_plain_next_token_cross_entropy():
    cfg = toy_cfg(passes=1, beta=0.0)
    params, ids = ref.init_params(cfg, 5), ids_for(5)
    model = toy_model(cfg)
    with jax.default_matmul_precision("highest"):
        logits = model.apply(params, {"input_ids": ids}, ["logits"])["logits"]
        got_loss, metrics = model.loss_and_metrics(params, {"input_ids": ids})
        ref_loss, _ = ref.row_losses(params, jnp.asarray(ids), cfg)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    plain = -jnp.mean(jnp.take_along_axis(
        logp, jnp.asarray(ids)[:, 1:, None], axis=-1)[..., 0], axis=-1)
    np.testing.assert_allclose(got_loss, plain, rtol=1e-6)
    np.testing.assert_allclose(ref_loss, plain, rtol=1e-5)
    np.testing.assert_allclose(metrics["exit_mass"], [1.0], rtol=1e-6)
    assert float(metrics["exit_entropy"]) == 0.0


def test_remat_on_and_off_agree(want, got):
    cfg, params, ids, _ = want
    plain = readings(toy_model(cfg, remat=False), cfg, params, ids)
    for name, value in got.items():
        np.testing.assert_allclose(plain[name], value, atol=1e-7, rtol=1e-5,
                                   err_msg=name)


def test_the_last_gate_enters_nothing_and_the_exit_masses_sum_to_one(want):
    """``p_T`` is what the gates before it leave: a row's exit distribution
    sums to one whatever the gates say."""
    cfg, params, ids, _ = want
    model = toy_model(cfg)
    moved = jax.tree.map(lambda a: a, params)
    moved["exit_gate"] = dict(kernel=params["exit_gate"]["kernel"] * 9.0,
                              bias=params["exit_gate"]["bias"] - 2.0)
    _, metrics = model.loss_and_metrics(moved, {"input_ids": ids})
    np.testing.assert_allclose(float(jnp.sum(metrics["exit_mass"])), 1.0,
                               rtol=1e-6)
    assert float(metrics["exit_entropy"]) > 0


def test_the_constructor_refuses_no_pass():
    with pytest.raises(ValueError, match="at least once"):
        toy_model(toy_cfg(), passes=0)


# -- through the normal path ---------------------------------------------------


def test_trainer_fits_it_on_the_fused_path_and_returns_its_counters():
    from sparkflow_tpu.trainer import Trainer

    cfg = toy_cfg()
    trainer = Trainer(build_registry_spec("looped_lm", **toy_kw(cfg)),
                      "input_ids", None, optimizer="adam", learning_rate=3e-3,
                      mini_batch_size=2, iters=2, shuffle_per_iter=False,
                      debug_recompiles=True, seed=1)
    rows = ids_for(1, rows=8)
    first = trainer.fit(rows.astype(np.float32),
                        init_params=ref.init_params(cfg, 1))
    again = trainer.fit(rows.astype(np.float32), init_params=trainer.params)
    assert again.losses[-1] < first.losses[0]
    # [sweeps, steps, ...]
    assert first.metrics["exit_mass"].shape == (2, 4, T)
    assert first.metrics["loop_loss"].shape == (2, 4, T)
    assert first.metrics["exit_entropy"].shape == (2, 4)
    np.testing.assert_allclose(first.metrics["exit_mass"].sum(axis=-1), 1.0,
                               rtol=1e-5)
    assert (first.metrics["loop_loss"][1] < first.metrics["loop_loss"][0]
            ).all()
    assert "no traced builds" in trainer.recompile_report
    served = trainer.predict_fn("logits")(trainer.params,
                                          rows[:2].astype(np.float32))
    assert np.asarray(served).shape == (2, S, VOCAB)      # the last pass's


def test_the_decode_plane_refuses_it_and_says_why():
    from sparkflow_tpu.serving.decode import DecodeEngine

    with pytest.raises(TypeError, match="trains only.*per \\(pass, layer\\)"):
        DecodeEngine(toy_model(toy_cfg()), None)


# -- the head it shares with the MoE families ----------------------------------


def _former_weighted_nll(self, params, x, tgt, weight):
    """``MoEDecoder._weighted_nll`` as it was before ``lm_ops.py``, kept here
    as the reference of the lift."""
    s = tgt.shape[0]
    c = self.head_block if s % self.head_block == 0 else s

    @jax.checkpoint
    def stretch(a):
        xs, t, w = a
        x_ = xs.astype(jnp.float32)
        x_ = (x_ * jax.lax.rsqrt(jnp.mean(jnp.square(x_), axis=-1,
                                          keepdims=True) + self.rms_eps)
              * params["final_ln"]["scale"]).astype(xs.dtype)
        logits = jnp.matmul(x_, params["lm_head"]["kernel"].astype(x_.dtype),
                            preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * w)

    split = lambda a: a.reshape((s // c, c) + a.shape[1:])
    return jnp.sum(jax.lax.map(stretch, (split(x), split(tgt), split(weight))))


def _moe_family(name):
    if name == "sparse_moe_lm":
        kw = dict(vocab_size=48, indexer_heads=2, indexer_dim=8,
                  indexer_topk=8, indexer_block=16, max_len=32, head_block=16)
        ids = np.random.default_rng(0).integers(0, 48, (2, 32))
    else:
        from sparkflow_tpu.models import noise_rows

        kw = dict(vocab_size=96, vocab_held=[0, 48], mask_token_id=90,
                  block_length=4, max_len=64)
        ids = noise_rows(np.random.default_rng(0).integers(0, 48, (2, 32)),
                         4, 90, 0)
    model = model_from_json(build_registry_spec(
        name, hidden=32, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=8, num_experts=8, experts_per_token=2, expert_dim=16,
        experts_held=[0, 4], rope_theta=1e4, **kw))
    return model, ids.astype(np.int32)


@pytest.mark.parametrize("dtype", [None, "bfloat16"])
@pytest.mark.parametrize("family", ["sparse_moe_lm", "block_diffusion_lm"])
def test_the_lifted_head_leaves_the_moe_families_losses_bit_equal(
        family, dtype, monkeypatch):
    model, ids = _moe_family(family)
    model.compute_dtype = dtype and jnp.dtype(dtype)
    params = model.init(jax.random.PRNGKey(2))
    value_and_grad = jax.jit(jax.value_and_grad(lambda p: jnp.mean(
        model.loss_vector(p, {"input_ids": ids}))))
    loss, grads = value_and_grad(params)
    monkeypatch.setattr(MoEDecoder, "_weighted_nll", _former_weighted_nll)
    jax.clear_caches()
    former_loss, former_grads = jax.jit(jax.value_and_grad(lambda p: jnp.mean(
        model.loss_vector(p, {"input_ids": ids}))))(params)
    assert float(loss) == float(former_loss) and np.isfinite(float(loss))
    for group in ("lm_head", "final_ln", "embed", "block_1"):
        for name, g in grads[group].items():
            np.testing.assert_array_equal(g, former_grads[group][name],
                                          err_msg=f"{group}/{name}")
