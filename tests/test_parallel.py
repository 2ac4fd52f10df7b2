"""Pipeline (pp) and expert (ep) parallelism + distributed helpers."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkflow_tpu.models import build_registry_spec, model_from_json
from sparkflow_tpu.optimizers import build_optimizer
from sparkflow_tpu.parallel.mesh import make_mesh, mesh_axis_size
from sparkflow_tpu.parallel.pp import (make_pp_train_step, merge_stage_params,
                                       pp_pspecs, split_stage_params)
from sparkflow_tpu.parallel.tp import filter_pspec, shard_params
from jax.sharding import PartitionSpec as P


@pytest.fixture(scope="module")
def pp_setup():
    spec = build_registry_spec("transformer_classifier", vocab_size=40,
                               num_classes=3, hidden=32, num_layers=8,
                               num_heads=4, mlp_dim=64, max_len=16, dropout=0.0)
    m = model_from_json(spec)
    params = m.init(jax.random.PRNGKey(0))
    return m, params


def test_stage_split_merge_roundtrip(pp_setup):
    m, params = pp_setup
    pp = split_stage_params(m, params, 4)
    back = merge_stage_params(m, pp)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_stage_split_copies_shared(pp_setup):
    m, params = pp_setup
    pp = split_stage_params(m, params, 4)
    # donation safety: shared leaves must not alias the caller's arrays
    assert pp["shared"]["embed"]["tok"] is not params["embed"]["tok"]


def test_pp_step_matches_single_device_and_trains(pp_setup):
    m, params = pp_setup
    mesh = make_mesh({"pp": 8})
    pp = shard_params(split_stage_params(m, params, 8), mesh,
                      pp_pspecs(split_stage_params(m, params, 8)))
    opt = build_optimizer("adam", 1e-3, None)
    state = opt.init(pp)
    step = make_pp_train_step(m, opt, mesh, n_microbatches=2)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 40, (8, 16)), jnp.int32)
    y = jnp.asarray(np.eye(3)[rs.randint(0, 3, 8)], jnp.float32)
    pp, state, loss = step(pp, state, ids, y, jax.random.PRNGKey(1))
    ref = m.loss_vector(params, {"input_ids": ids, "y": y}, train=False).mean()
    np.testing.assert_allclose(float(loss), float(ref), atol=1e-4)
    first = float(loss)
    for i in range(6):
        pp, state, loss = step(pp, state, ids, y, jax.random.PRNGKey(i + 2))
    assert float(loss) < first


def test_pp_gpipe_matches_sequential_schedule(pp_setup):
    """The overlapped gpipe schedule must be a pure scheduling change: same
    loss and same updated params as the sequential baseline, with the serial
    span cut from M*P to M+P-1 stage-times."""
    m, params = pp_setup
    mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
    opt = build_optimizer("gradient_descent", 0.1, None)
    rs = np.random.RandomState(1)
    ids = jnp.asarray(rs.randint(0, 40, (8, 16)), jnp.int32)
    y = jnp.asarray(np.eye(3)[rs.randint(0, 3, 8)], jnp.float32)

    results = {}
    for sched in ("gpipe", "1f1b", "sequential"):
        pp = shard_params(split_stage_params(m, params, 4), mesh,
                          pp_pspecs(split_stage_params(m, params, 4)))
        step = make_pp_train_step(m, opt, mesh, n_microbatches=4,
                                  schedule=sched)
        p2, _, loss = step(pp, opt.init(pp), ids, y, jax.random.PRNGKey(7))
        results[sched] = (float(loss), merge_stage_params(m, p2))

    for sched in ("gpipe", "1f1b"):
        assert results[sched][0] == pytest.approx(results["sequential"][0],
                                                  rel=1e-5), sched
        for a, b in zip(jax.tree.leaves(results[sched][1]),
                        jax.tree.leaves(results["sequential"][1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=sched)
    # schedule property: 4 microbatches over 4 stages
    g = make_pp_train_step(m, opt, mesh, n_microbatches=4, schedule="gpipe")
    f = make_pp_train_step(m, opt, mesh, n_microbatches=4, schedule="1f1b")
    s = make_pp_train_step(m, opt, mesh, n_microbatches=4, schedule="sequential")
    assert g.schedule_ticks == 7 and s.schedule_ticks == 16
    # 1f1b table counts COMBINED fwd+bwd slots: ~2M + 2P - 3
    assert f.schedule_ticks == 14


def test_pp_1f1b_schedule_tables():
    """The simulated schedule has the canonical 1F1B shape: per-stage
    in-flight peaks at exactly min(M, P - s), every microbatch runs fwd+bwd
    exactly once per stage, and cotangents arrive on their consumption
    tick."""
    from sparkflow_tpu.parallel.pp import (_OP_BWD, _OP_FWD, _simulate_1f1b)

    for P, M in ((2, 2), (4, 4), (4, 8), (8, 16), (3, 5)):
        ops, mbs, arrf, arrm = _simulate_1f1b(P, M)
        for s in range(P):
            f = b = peak = 0
            for t in range(ops.shape[0]):
                if ops[t, s] == _OP_FWD:
                    f += 1
                if ops[t, s] == _OP_BWD:
                    b += 1
                peak = max(peak, f - b)
            # last stage's FWD ops are rewritten to NONE (arrival-stored)
            assert b == M, (P, M, s)
            if s < P - 1:
                assert f == M, (P, M, s)
                assert peak == min(M, P - s), (P, M, s, peak)


def test_moe_ep_sharding_matches_replicated():
    spec = build_registry_spec("transformer_moe_lm", vocab_size=40,
                               num_experts=8, hidden=32, num_layers=2,
                               num_heads=4, mlp_dim=64, max_len=16, dropout=0.0)
    m = model_from_json(spec)
    params = m.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 40, (4, 16)), jnp.int32)
    mesh = make_mesh({"ep": 8})
    sp = shard_params(params, mesh, m.param_pspecs())
    assert "ep" in str(sp["block_1"]["experts_fc1"].sharding.spec)

    def loss_fn(p):
        return m.loss_vector(p, {"input_ids": ids}, train=False).mean()

    np.testing.assert_allclose(float(loss_fn(params)),
                               float(jax.jit(loss_fn)(sp)), rtol=1e-5)


def test_moe_capacity_dispatch_matches_per_token_ffn():
    # with capacity >= tokens-per-expert nothing drops: routed output must
    # equal the per-token expert FFN times the gate, computed by hand
    spec = build_registry_spec("transformer_moe_lm", vocab_size=20,
                               num_experts=4, moe_every=1, hidden=16,
                               num_layers=1, num_heads=2, mlp_dim=32,
                               max_len=8, dropout=0.0, capacity_factor=4.0)
    m = model_from_json(spec)
    bp = m.init(jax.random.PRNGKey(0))["block_0"]
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(2, 8, 16), jnp.float32)
    y, aux = m._moe_mlp(bp, x)
    xf = np.asarray(x).reshape(-1, 16)
    logits = xf @ np.asarray(bp["router"])
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    idx = probs.argmax(-1)
    expect = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        e = idx[t]
        h = np.asarray(jax.nn.gelu(jnp.asarray(
            xf[t] @ np.asarray(bp["experts_fc1"])[e] + np.asarray(bp["experts_b1"])[e])))
        expect[t] = (h @ np.asarray(bp["experts_fc2"])[e]
                     + np.asarray(bp["experts_b2"])[e]) * probs[t, e]
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 16), expect,
                               rtol=1e-4, atol=1e-5)
    assert float(aux) > 0.0


def test_moe_capacity_dispatch_drops_overflow_tokens():
    spec = build_registry_spec("transformer_moe_lm", vocab_size=20,
                               num_experts=4, moe_every=1, hidden=16,
                               num_layers=1, num_heads=2, mlp_dim=32,
                               max_len=8, dropout=0.0, capacity_factor=0.5)
    m = model_from_json(spec)
    params = m.init(jax.random.PRNGKey(0))
    bp = dict(params["block_0"])
    # force every token onto expert 2 with positive inputs -> argmax is col 2
    router = np.zeros((16, 4), np.float32)
    router[:, 2] = 10.0
    bp["router"] = jnp.asarray(router)
    rs = np.random.RandomState(2)
    x = jnp.asarray(np.abs(rs.randn(1, 8, 16)) + 0.1, jnp.float32)
    y, _ = m._moe_mlp(bp, x)
    y = np.asarray(y).reshape(8, 16)
    # capacity = ceil(0.5 * 8 / 4) = 1: first token served, rest dropped to 0
    assert np.abs(y[0]).max() > 0
    np.testing.assert_array_equal(y[1:], np.zeros_like(y[1:]))


def test_moe_masked_tokens_claim_no_capacity():
    """Padding tokens (attention_mask 0) must not occupy expert slots: with a
    tight capacity, identical pad rows would otherwise flood one expert and
    evict real tokens that arrive later in flat order."""
    spec = build_registry_spec("transformer_moe_lm", vocab_size=20,
                               num_experts=4, moe_every=1, hidden=16,
                               num_layers=1, num_heads=2, mlp_dim=32,
                               max_len=8, dropout=0.0, capacity_factor=1.0)
    m = model_from_json(spec)
    params = m.init(jax.random.PRNGKey(0))
    bp = dict(params["block_0"])
    router = np.zeros((16, 4), np.float32)
    router[:, 1] = 10.0  # everything wants expert 1; capacity = 8*1/4 = 2
    bp["router"] = jnp.asarray(router)
    rs = np.random.RandomState(0)
    x = jnp.asarray(np.abs(rs.randn(1, 8, 16)) + 0.1, jnp.float32)
    # first 6 tokens are padding, last 2 are real
    mask = jnp.asarray([[0, 0, 0, 0, 0, 0, 1, 1]], jnp.float32)
    y, aux = m._moe_mlp(bp, x, token_mask=mask)
    y = np.asarray(y).reshape(8, 16)
    # pad tokens produce nothing and claim nothing; both real tokens fit
    np.testing.assert_array_equal(y[:6], np.zeros_like(y[:6]))
    assert np.abs(y[6]).max() > 0 and np.abs(y[7]).max() > 0
    # without the mask, the pad flood evicts the real tokens (sanity check
    # that the scenario is the one the mask is protecting against)
    y2, _ = m._moe_mlp(bp, x)
    y2 = np.asarray(y2).reshape(8, 16)
    assert np.abs(y2[6:]).max() == 0


def test_moe_flops_scale_with_tokens_not_experts():
    # capacity routing: expert FLOPs follow the token count, not E; the old
    # all-experts einsum made the E=8 model ~4x the E=2 model's FLOPs
    def flops(num_experts):
        spec = build_registry_spec("transformer_moe_lm", vocab_size=20,
                                   num_experts=num_experts, moe_every=1,
                                   hidden=64, num_layers=2, num_heads=2,
                                   mlp_dim=512, max_len=32, dropout=0.0)
        m = model_from_json(spec)
        params = m.init(jax.random.PRNGKey(0))
        ids = jnp.zeros((4, 32), jnp.int32)

        def loss(p):
            return m.loss_vector(p, {"input_ids": ids}, train=False).mean()

        ca = jax.jit(loss).lower(params).compile().cost_analysis()
        if isinstance(ca, list):  # pre-0.6 jax: one dict per computation
            ca = ca[0]
        return ca["flops"]

    assert flops(8) < 1.6 * flops(2)


def test_moe_aux_loss_encourages_balance():
    spec = build_registry_spec("transformer_moe_lm", vocab_size=20,
                               num_experts=4, hidden=16, num_layers=2,
                               num_heads=2, mlp_dim=32, max_len=8,
                               dropout=0.0, router_aux_weight=0.0)
    m0 = model_from_json(spec)
    params = m0.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 20, (4, 8)), jnp.int32)
    base = float(m0.loss_vector(params, {"input_ids": ids}, train=False).mean())
    spec1 = build_registry_spec("transformer_moe_lm", vocab_size=20,
                                num_experts=4, hidden=16, num_layers=2,
                                num_heads=2, mlp_dim=32, max_len=8,
                                dropout=0.0, router_aux_weight=0.5)
    m1 = model_from_json(spec1)
    with_aux = float(m1.loss_vector(params, {"input_ids": ids}, train=False).mean())
    assert with_aux > base  # aux term present (>= 1.0 * weight by construction)


def test_filter_pspec_drops_unknown_axes():
    mesh = make_mesh({"ep": 8})
    assert filter_pspec(P(None, "tp"), mesh) == P(None, None)
    assert filter_pspec(P("ep", None), mesh) == P("ep", None)
    assert mesh_axis_size(mesh, "ep") == 8
    assert mesh_axis_size(mesh, "tp") == 1


def test_distributed_helpers_single_process():
    from sparkflow_tpu.parallel import distributed as dist
    dist.initialize()  # no-op in single process
    mesh = dist.global_mesh({"dp": -1})
    assert mesh.devices.size == len(jax.devices())
    assert dist.process_local_batch(64) == 64
    assert ":" in dist.determine_master()


def test_moe_top2_routing_matches_per_token_mixture():
    """router_top_k=2 (GShard style): with ample capacity each token's output
    is the gate-weighted mixture of its two chosen experts' FFNs."""
    spec = build_registry_spec("transformer_moe_lm", vocab_size=20,
                               num_experts=4, moe_every=1, hidden=16,
                               num_layers=1, num_heads=2, mlp_dim=32,
                               max_len=8, dropout=0.0, capacity_factor=4.0,
                               router_top_k=2)
    m = model_from_json(spec)
    bp = m.init(jax.random.PRNGKey(0))["block_0"]
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(2, 8, 16), jnp.float32)
    y, aux = m._moe_mlp(bp, x)
    xf = np.asarray(x).reshape(-1, 16)
    probs = np.asarray(jax.nn.softmax(
        jnp.asarray(xf @ np.asarray(bp["router"])), axis=-1))
    expect = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        top2 = np.argsort(probs[t])[::-1][:2]
        g = probs[t, top2] / probs[t, top2].sum()
        for gi, ei in zip(g, top2):
            hmid = np.asarray(jax.nn.gelu(jnp.asarray(
                xf[t] @ np.asarray(bp["experts_fc1"])[ei]
                + np.asarray(bp["experts_b1"])[ei])))
            expect[t] += gi * (hmid @ np.asarray(bp["experts_fc2"])[ei]
                               + np.asarray(bp["experts_b2"])[ei])
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 16), expect,
                               rtol=1e-4, atol=1e-5)
    assert float(aux) > 0


def test_moe_top2_trains_and_shards():
    spec = build_registry_spec("transformer_moe_lm", vocab_size=30,
                               num_experts=8, moe_every=1, hidden=16,
                               num_layers=2, num_heads=2, mlp_dim=32,
                               max_len=8, dropout=0.0, router_top_k=2)
    m = model_from_json(spec)
    mesh = make_mesh({"ep": 8})
    params = shard_params(m.init(jax.random.PRNGKey(0)), mesh, m.param_pspecs())
    opt = build_optimizer("adam", 1e-2, None)
    state = opt.init(params)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 30, (4, 8)), jnp.int32)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(lambda p: m.loss_vector(
            p, {"input_ids": ids}, train=False).mean())(p)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, l

    first = None
    for i in range(8):
        params, state, l = step(params, state)
        first = first if first is not None else float(l)
    assert float(l) < first


def test_moe_all_to_all_shardmap_matches_replicated():
    """The shard_map all_to_all EP path (GShard pipeline: route -> exchange
    -> local experts -> exchange back) must match the single-device
    capacity-dispatch model with the same weights, and train."""
    from sparkflow_tpu.parallel.ep import (make_moe_shardmap_train_step,
                                           place_moe_params)

    mesh = make_mesh({"ep": 8})
    kw = dict(vocab_size=40, num_experts=8, moe_every=1, hidden=32,
              num_layers=2, num_heads=4, mlp_dim=64, max_len=16,
              dropout=0.0, capacity_factor=8.0)
    m_a2a = model_from_json(build_registry_spec("transformer_moe_lm",
                                                ep_axis="ep", **kw))
    m_ref = model_from_json(build_registry_spec("transformer_moe_lm", **kw))
    params = m_ref.init(jax.random.PRNGKey(0))

    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 40, (16, 16)), jnp.int32)
    mask = jnp.ones((16, 16), jnp.float32)

    opt = build_optimizer("gradient_descent", 0.05, None)
    placed = place_moe_params(m_a2a, jax.tree.map(jnp.copy, params), mesh)
    step = make_moe_shardmap_train_step(m_a2a, opt, mesh)
    state = opt.init(placed)
    placed, state, loss = step(placed, state, ids, mask, jax.random.PRNGKey(1))

    ref_loss = m_ref.loss_vector(
        params, {"input_ids": ids, "attention_mask": mask},
        train=False).mean()
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-4)

    first = float(loss)
    for i in range(5):
        placed, state, loss = step(placed, state, ids, mask,
                                   jax.random.PRNGKey(i + 2))
    assert float(loss) < first
    # expert shards stayed sharded through the update
    assert "ep" in str(placed["block_0"]["experts_fc1"].sharding.spec)


def test_moe_a2a_top2_matches_gspmd_top2():
    """The all_to_all dispatch at router_top_k=2 must match the GSPMD
    capacity-dispatch model with the same weights (capacity covers every
    choice, so neither form drops tokens)."""
    from sparkflow_tpu.parallel.ep import (make_moe_shardmap_train_step,
                                           place_moe_params)

    mesh = make_mesh({"ep": 8})
    kw = dict(vocab_size=40, num_experts=8, moe_every=1, hidden=32,
              num_layers=2, num_heads=4, mlp_dim=64, max_len=16,
              dropout=0.0, capacity_factor=8.0, router_top_k=2)
    m_a2a = model_from_json(build_registry_spec("transformer_moe_lm",
                                                ep_axis="ep", **kw))
    m_ref = model_from_json(build_registry_spec("transformer_moe_lm", **kw))
    params = m_ref.init(jax.random.PRNGKey(0))

    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 40, (16, 16)), jnp.int32)
    mask = jnp.ones((16, 16), jnp.float32)

    opt = build_optimizer("gradient_descent", 0.05, None)
    placed = place_moe_params(m_a2a, jax.tree.map(jnp.copy, params), mesh)
    step = make_moe_shardmap_train_step(m_a2a, opt, mesh)
    p2, _, loss = step(placed, opt.init(placed), ids, mask,
                       jax.random.PRNGKey(1))

    ref_loss = m_ref.loss_vector(
        params, {"input_ids": ids, "attention_mask": mask},
        train=False).mean()
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-4)
    # the one-step update matches the replicated model's update too
    import optax
    g = jax.grad(lambda p: m_ref.loss_vector(
        p, {"input_ids": ids, "attention_mask": mask},
        train=False).mean())(params)
    sgd = optax.apply_updates(params, jax.tree.map(lambda x: -0.05 * x, g))
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(sgd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


def test_moe_a2a_overflow_fraction_metric():
    """return_overflow reports the dropped fraction: generous capacity -> 0;
    a starved capacity_factor must drop a nonzero fraction of choices."""
    from functools import partial

    from sparkflow_tpu.ops.moe_dispatch import all_to_all_moe_ffn

    mesh = make_mesh({"ep": 4}, devices=jax.devices()[:4])
    e, h, m = 4, 8, 16
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(8, 4, h), jnp.float32)
    router = jnp.asarray(rs.randn(h, e), jnp.float32)
    fc1 = jnp.asarray(rs.randn(e, h, m) * 0.1, jnp.float32)
    b1 = jnp.zeros((e, m), jnp.float32)
    fc2 = jnp.asarray(rs.randn(e, m, h) * 0.1, jnp.float32)
    b2 = jnp.zeros((e, h), jnp.float32)

    def run(cf):
        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep"), P("ep")),
                 out_specs=(P("ep"), P("ep"), P("ep")),
                 check_vma=False)
        def f(x, router, fc1, b1, fc2, b2):
            y, aux, ovf = all_to_all_moe_ffn(
                x, router, fc1, b1, fc2, b2, "ep", e, capacity_factor=cf,
                top_k=2, return_overflow=True)
            return y, aux[None], ovf[None]
        return f(x, router, fc1, b1, fc2, b2)

    _, _, ovf_generous = run(float(e))
    assert float(jnp.max(ovf_generous)) == 0.0
    _, _, ovf_tight = run(0.25)
    assert float(jnp.mean(ovf_tight)) > 0.05


def test_moe_a2a_outside_shardmap_fails_actionably():
    m = model_from_json(build_registry_spec(
        "transformer_moe_lm", vocab_size=20, num_experts=4, moe_every=1,
        ep_axis="ep", hidden=16, num_layers=1, num_heads=2, mlp_dim=32,
        max_len=8, dropout=0.0))
    p = m.init(jax.random.PRNGKey(0))
    ids = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(NameError, match="make_moe_shardmap_train_step"):
        m.loss_vector(p, {"input_ids": ids}, train=False)


def test_pp_composes_with_dp(pp_setup):
    """pp(4) x dp(2): batch sharded over dp, stages over pp — one step must
    match the single-device loss/update (dropout 0, equal shards)."""
    m, params = pp_setup
    mesh = make_mesh({"dp": 2, "pp": 4})
    opt = build_optimizer("gradient_descent", 0.1, None)
    rs = np.random.RandomState(2)
    ids = jnp.asarray(rs.randint(0, 40, (8, 16)), jnp.int32)
    y = jnp.asarray(np.eye(3)[rs.randint(0, 3, 8)], jnp.float32)

    pp = shard_params(split_stage_params(m, params, 4), mesh,
                      pp_pspecs(split_stage_params(m, params, 4)))
    step = make_pp_train_step(m, opt, mesh, n_microbatches=2)
    p2, _, loss = step(pp, opt.init(pp), ids, y, jax.random.PRNGKey(5))
    ref = m.loss_vector(params, {"input_ids": ids, "y": y},
                        train=False).mean()
    np.testing.assert_allclose(float(loss), float(ref), atol=1e-4)

    # the update equals plain single-device SGD on the same global batch
    import optax
    def ref_loss(p):
        return m.loss_vector(p, {"input_ids": ids, "y": y},
                             train=False).mean()
    g = jax.grad(ref_loss)(params)
    sgd_params = optax.apply_updates(params, jax.tree.map(lambda x: -0.1 * x, g))
    back = merge_stage_params(m, p2)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(sgd_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("sched", ["gpipe", "1f1b"])
def test_pp_lm_task_matches_single_device(sched):
    """Pipeline-parallel causal LM (task='lm'): loss and the SGD update must
    match the single-device transformer_lm on the same batch."""
    import optax
    spec = build_registry_spec("transformer_lm", vocab_size=40, hidden=32,
                               num_layers=8, num_heads=4, mlp_dim=64,
                               max_len=16, dropout=0.0)
    m = model_from_json(spec)
    params = m.init(jax.random.PRNGKey(0))
    mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
    pp = shard_params(split_stage_params(m, params, 4), mesh,
                      pp_pspecs(split_stage_params(m, params, 4)))
    opt = build_optimizer("gradient_descent", 0.1, None)
    step = make_pp_train_step(m, opt, mesh, n_microbatches=2, task="lm",
                              schedule=sched)
    rs = np.random.RandomState(3)
    ids = jnp.asarray(rs.randint(0, 40, (8, 16)), jnp.int32)
    mask = jnp.ones((8, 16), jnp.float32)
    p2, _, loss = step(pp, opt.init(pp), ids, mask, jax.random.PRNGKey(9))

    def ref_loss(p):
        return m.loss_vector(p, {"input_ids": ids, "attention_mask": mask},
                             train=False).mean()

    np.testing.assert_allclose(float(loss), float(ref_loss(params)),
                               atol=1e-4)
    g = jax.grad(ref_loss)(params)
    sgd = optax.apply_updates(params, jax.tree.map(lambda x: -0.1 * x, g))
    back = merge_stage_params(m, p2)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(sgd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


def test_hierarchical_psum_mean_matches_flat():
    """The DCN-aware two-level reduction (reduce_scatter over ICI -> psum
    the 1/n_ici shard over DCN -> all_gather) equals a flat psum-mean over
    both axes exactly — incl. leaves whose size does not divide the ICI
    axis (flat-pad path)."""

    from sparkflow_tpu.parallel.collectives import hierarchical_psum_mean

    mesh = make_mesh({"dcn": 2, "dp": 4})
    rs = np.random.RandomState(0)
    # 7 and 10 don't divide dp=4; (3,5) exercises reshape; scalar-ish leaf too
    tree = {"a": jnp.asarray(rs.randn(7), jnp.float32),
            "b": jnp.asarray(rs.randn(3, 5), jnp.float32),
            "c": jnp.asarray(rs.randn(8), jnp.float32)}

    def per_device(seed_tree):
        # each device contributes a deterministic distinct tree
        i = jax.lax.axis_index("dcn") * 4 + jax.lax.axis_index("dp")
        contrib = jax.tree.map(lambda x: x * (1.0 + i), seed_tree)
        hier = hierarchical_psum_mean(contrib, ici_axis="dp", dcn_axis="dcn")
        flat = jax.tree.map(
            lambda x: jax.lax.psum(x, ("dcn", "dp")) / 8.0, contrib)
        return hier, flat

    hier, flat = jax.jit(jax.shard_map(
        per_device, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
        check_vma=False))(tree)
    for k in tree:
        np.testing.assert_allclose(np.asarray(hier[k]), np.asarray(flat[k]),
                                   rtol=1e-6)


def test_dp_shardmap_two_level_matches_flat():
    """make_dp_shardmap_train_step(dcn_axis=...) on a {dcn,dp} mesh: one
    step's updated params equal the flat single-axis dp step's on the same
    batch — the hierarchical wire layout changes traffic, not math."""
    from sparkflow_tpu.parallel.dp import make_dp_shardmap_train_step

    spec = build_registry_spec("transformer_classifier", vocab_size=32,
                               num_classes=3, hidden=32, num_layers=2,
                               num_heads=4, mlp_dim=64, max_len=8,
                               dropout=0.0)
    m = model_from_json(spec)
    opt = build_optimizer("adam", 1e-3, None)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 32, (16, 8)), jnp.float32)
    y = jnp.asarray(np.eye(3, dtype=np.float32)[rs.randint(0, 3, 16)])
    mask = jnp.ones((16,), jnp.float32)
    p0 = m.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)

    mesh2 = make_mesh({"dcn": 2, "dp": 4})
    step2 = make_dp_shardmap_train_step(m, opt, mesh2, "input_ids", "y",
                                        dcn_axis="dcn")
    p_a = jax.tree.map(jnp.array, p0)
    p_a, _, loss_a = step2(p_a, opt.init(p_a), ids, y, mask, rng)

    # flat reference on a 1-axis mesh with the same total devices: dropout
    # is off and grads are exact means, so device-index rng folds don't
    # enter the update math
    mesh1 = make_mesh({"dp": 8})
    step1 = make_dp_shardmap_train_step(m, opt, mesh1, "input_ids", "y")
    p_b = jax.tree.map(jnp.array, p0)
    p_b, _, loss_b = step1(p_b, opt.init(p_b), ids, y, mask, rng)

    assert abs(float(loss_a) - float(loss_b)) < 1e-5
    for ka in p_a:
        for la, lb in zip(jax.tree.leaves(p_a[ka]), jax.tree.leaves(p_b[ka])):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       atol=5e-5)
