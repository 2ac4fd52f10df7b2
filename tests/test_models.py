"""Registry models: transformer (clf + LM), ResNet, presets, TP/SP steps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkflow_tpu.models import (build_registry_spec, model_from_json, presets)
from sparkflow_tpu.optimizers import build_optimizer
from sparkflow_tpu.parallel.mesh import make_mesh
from sparkflow_tpu.parallel.sp import make_sp_train_step
from sparkflow_tpu.parallel.tp import (fsdp_pspecs, make_sharded_train_step,
                                       shard_params)
from sparkflow_tpu.trainer import Trainer


TINY_CLF = dict(vocab_size=64, num_classes=3, hidden=32, num_layers=2,
                num_heads=4, mlp_dim=64, max_len=16)


def test_registry_spec_roundtrip():
    spec = build_registry_spec("transformer_classifier", **TINY_CLF)
    m = model_from_json(spec)
    assert m.model_name == "transformer_classifier"
    with pytest.raises(KeyError):
        build_registry_spec("not_a_model")


def test_transformer_classifier_trains():
    spec = build_registry_spec("transformer_classifier", **TINY_CLF)
    rs = np.random.RandomState(0)
    # learnable: class = first token id % 3
    ids = rs.randint(0, 64, (128, 16)).astype(np.float32)
    labels = (ids[:, 0] % 3).astype(int)
    y = np.eye(3)[labels].astype(np.float32)
    tr = Trainer(spec, "input_ids:0", "y:0", iters=30, mini_batch_size=32,
                 learning_rate=3e-3)
    res = tr.fit(ids, y)
    assert res.losses[-1] < res.losses[0]


def test_transformer_lm_loss_decreases():
    spec = build_registry_spec("transformer_lm", vocab_size=32, hidden=32,
                               num_layers=2, num_heads=4, mlp_dim=64, max_len=16)
    m = model_from_json(spec)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(np.tile(np.arange(16), (8, 1)), jnp.int32)  # predictable
    params = m.init(jax.random.PRNGKey(0))
    opt = build_optimizer("adam", 1e-2, None)
    state = opt.init(params)
    import optax

    @jax.jit
    def step(params, state):
        def lf(p):
            return m.loss_vector(p, {"input_ids": ids},
                                 rng=jax.random.PRNGKey(1)).mean()
        loss, g = jax.value_and_grad(lf)(params)
        u, state2 = opt.update(g, state, params)
        return optax.apply_updates(params, u), state2, loss

    losses = []
    for _ in range(20):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8


def test_tp_sharded_step(dp_mesh):
    mesh = make_mesh({"dp": 2, "tp": 4})
    spec = build_registry_spec("transformer_classifier", **TINY_CLF)
    m = model_from_json(spec)
    params = shard_params(m.init(jax.random.PRNGKey(0)), mesh, m.param_pspecs())
    opt = build_optimizer("adam", 1e-3, None)
    state = opt.init(params)
    step = make_sharded_train_step(m, opt, mesh, "input_ids", "y")
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 64, (4, 16)), jnp.float32)
    y = jnp.asarray(np.eye(3)[rs.randint(0, 3, 4)], jnp.float32)
    mask = jnp.ones((4,), jnp.float32)
    p2, s2, loss = step(params, state, ids, y, mask, jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    # param shardings survived the update
    qkv = p2["block_0"]["qkv_kernel"]
    assert "tp" in str(qkv.sharding.spec)


def test_sp_ring_step_matches_single_device_loss():
    mesh = make_mesh({"dp": 2, "sp": 4})
    spec = build_registry_spec("transformer_lm", vocab_size=50, hidden=32,
                               num_layers=2, num_heads=4, mlp_dim=64,
                               max_len=32, dropout=0.0)
    lm = model_from_json(spec)
    params = lm.init(jax.random.PRNGKey(0))
    opt = build_optimizer("adam", 1e-3, None)
    step = make_sp_train_step(lm, opt, mesh)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 50, (4, 32)), jnp.int32)
    mask = jnp.ones((4, 32), jnp.float32)
    _, _, loss = step(jax.tree.map(jnp.copy, params), opt.init(params), ids,
                      mask, jax.random.PRNGKey(3))
    single = model_from_json(spec)
    ref = single.loss_vector(params, {"input_ids": ids, "attention_mask": mask},
                             train=False).mean()
    # shard-boundary targets are excluded under sp, so tolerances are loose
    assert abs(float(loss) - float(ref)) < 0.1


def test_sp_forward_matches_single_device_logits():
    """Regression: under sp, shard i must use GLOBAL positions i*S_local..;
    amplified pos table + trained-scale comparison catches local-offset bugs."""
    import copy
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh({"sp": 8})
    spec = build_registry_spec("transformer_lm", vocab_size=50, hidden=32,
                               num_layers=2, num_heads=4, mlp_dim=64,
                               max_len=32, dropout=0.0)
    lm = model_from_json(spec)
    params = lm.init(jax.random.PRNGKey(0))
    params["embed"]["pos"] = params["embed"]["pos"] * 5.0  # amplify position signal

    lm_sp = copy.copy(lm)
    lm_sp.sp_axis = "sp"
    fwd = jax.shard_map(
        lambda p, ids: lm_sp.apply(p, {"input_ids": ids}, ["logits"])["logits"],
        mesh=mesh, in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp", None), check_vma=False)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 50, (2, 32)), jnp.int32)
    sp_logits = jax.jit(fwd)(params, ids)
    ref_logits = lm.apply(params, {"input_ids": ids}, ["logits"])["logits"]
    np.testing.assert_allclose(np.asarray(sp_logits), np.asarray(ref_logits),
                               atol=1e-3)


def test_ring_attention_respects_kv_mask():
    from jax.sharding import PartitionSpec as P
    from sparkflow_tpu.ops import attention_reference, ring_attention
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()), ("sp",))
    rs = np.random.RandomState(0)
    B, H, S, D = 2, 2, 64, 16
    q = jnp.asarray(rs.randn(B, H, S, D), jnp.float32)
    mask = jnp.asarray((rs.rand(B, S) > 0.3).astype(np.float32))

    ring = jax.shard_map(
        lambda q, k, v, m: ring_attention(q, k, v, "sp", kv_mask=m),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3 + (P(None, "sp"),),
        out_specs=P(None, None, "sp", None), check_vma=False)
    out = jax.jit(ring)(q, q, q, mask)
    # reference with additive key mask
    s = jnp.einsum("bhqd,bhkd->bhqk", q, q) / np.sqrt(D)
    s = jnp.where(mask[:, None, None, :] > 0, s, -1e30)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_sp_step_does_not_mutate_model():
    spec = build_registry_spec("transformer_lm", vocab_size=20, hidden=16,
                               num_layers=1, num_heads=2, mlp_dim=32, max_len=16)
    lm = model_from_json(spec)
    mesh = make_mesh({"dp": 2, "sp": 4})
    make_sp_train_step(lm, build_optimizer("adam", 1e-3, None), mesh)
    assert lm.sp_axis is None  # caller's model untouched
    # and still usable outside shard_map
    p = lm.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.randint(0, 20, (2, 16)), jnp.int32)
    assert np.isfinite(float(lm.loss_vector(p, {"input_ids": ids}).mean()))


def test_fsdp_pspecs_shard_large_only():
    spec = build_registry_spec("transformer_classifier", **TINY_CLF)
    m = model_from_json(spec)
    specs = fsdp_pspecs(m.param_specs(), min_size=32 * 96)
    assert "fsdp" in str(specs["block_0"]["qkv_kernel"])
    assert str(specs["block_0"]["ln1_scale"]) == "PartitionSpec()"


def test_resnet_variants():
    for depth, np_expect in ((18, None), (50, None)):
        m = model_from_json(build_registry_spec("resnet", num_classes=10,
                                                depth=depth, image_size=32))
        p = m.init(jax.random.PRNGKey(0))
        x = np.random.rand(2, 32, 32, 3).astype(np.float32)
        out = m.apply(p, {"x": x}, ["logits:0", "pred:0"])
        assert out["logits:0"].shape == (2, 10)
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(p))
    assert 20e6 < total < 30e6  # ResNet-50 ~23.5M params


@pytest.mark.slow  # ~80s: full resnet-18 Trainer fit; run by path when
# touching models/resnet or conv lowering
def test_resnet_trains_via_trainer():
    spec = build_registry_spec("resnet", num_classes=2, depth=18, image_size=8)
    rs = np.random.RandomState(0)
    x = rs.rand(32, 8, 8, 3).astype(np.float32)
    labels = (x.mean(axis=(1, 2, 3)) > 0.5).astype(int)
    y = np.eye(2)[labels].astype(np.float32)
    tr = Trainer(spec, "x:0", "y:0", iters=5, mini_batch_size=16,
                 learning_rate=0.01)
    res = tr.fit(x.reshape(32, -1).reshape(32, 8, 8, 3), y)
    assert np.isfinite(res.losses[-1])


def test_presets_build():
    for spec in (presets.mlp(20, 3), presets.cnn(28, 1, 10),
                 presets.autoencoder(50, (16, 4, 16))):
        m = model_from_json(spec)
        p = m.init(jax.random.PRNGKey(0))
        assert p


def test_tp_sharded_step_with_pallas_eligible_shapes():
    """Seq/head shapes that satisfy the pallas tiling constraints must still
    compile + run under a tp x dp sharded jit: the trace guard forces the
    GSPMD-partitionable blockwise attention path (ADVICE r1, tp.py:77)."""
    mesh = make_mesh({"dp": 4, "tp": 2})
    spec = build_registry_spec("transformer_classifier", vocab_size=64,
                               num_classes=3, hidden=32, num_layers=2,
                               num_heads=4, mlp_dim=64, max_len=128,
                               dropout=0.0)
    m = model_from_json(spec)
    params = m.init(jax.random.PRNGKey(0))
    sharded = shard_params(jax.tree.map(jnp.copy, params), mesh, m.param_pspecs())
    opt = build_optimizer("adam", 1e-3, None)
    step = make_sharded_train_step(m, opt, mesh, "input_ids", "y")
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 64, (8, 128)), jnp.float32)
    y = jnp.asarray(np.eye(3)[rs.randint(0, 3, 8)], jnp.float32)
    mask = jnp.ones((8,), jnp.float32)
    _, _, loss = step(sharded, opt.init(sharded), ids, y, mask,
                      jax.random.PRNGKey(1))
    ref = m.loss_vector(params, {"input_ids": ids, "y": y},
                        train=False).mean()
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-4, atol=1e-4)


def test_dp_shardmap_step_matches_gspmd_and_runs_pallas():
    """shard_map DP step: same numerics as the GSPMD step, and the pallas
    flash-attention kernel actually executes (operands are device-local, so
    no GSPMD partitioning rule is needed — the multi-chip kernel path)."""
    from sparkflow_tpu.core import make_loss_fn, make_train_step
    from sparkflow_tpu.ops import attention as A
    from sparkflow_tpu.parallel.dp import make_dp_shardmap_train_step

    mesh = make_mesh({"dp": 8})
    spec = build_registry_spec("transformer_classifier", vocab_size=32,
                               num_classes=3, hidden=32, num_layers=2,
                               num_heads=4, mlp_dim=64, max_len=128,
                               dropout=0.0)
    m = model_from_json(spec)
    params = m.init(jax.random.PRNGKey(0))
    opt = build_optimizer("gradient_descent", 0.1, None)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 32, (8, 128)), jnp.float32)
    y = jnp.asarray(np.eye(3)[rs.randint(0, 3, 8)], jnp.float32)
    mask = jnp.ones((8,), jnp.float32)

    calls = []
    orig = A._flash_pallas_forward

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    A._flash_pallas_forward = spy
    try:
        step = make_dp_shardmap_train_step(m, opt, mesh, "input_ids", "y")
        p1, _, l1 = step(jax.tree.map(jnp.copy, params), opt.init(params),
                         ids, y, mask, jax.random.PRNGKey(1))
    finally:
        A._flash_pallas_forward = orig
    assert calls, "pallas kernel was not reached under shard_map"

    gstep = make_train_step(make_loss_fn(m, "input_ids", "y"), opt, mesh)
    p2, _, l2 = gstep(jax.tree.map(jnp.copy, params), opt.init(params),
                      ids, y, mask, jax.random.PRNGKey(1))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_sp_step_gradients_exact_vs_masked_reference():
    """Pin sp gradients exactly: a single-device reference computing the SAME
    loss (per-shard next-token NLL, shard-boundary targets excluded) must
    produce the same loss and the same SGD update as the sp step."""
    import optax
    n_sp = 4
    S = 32
    Sl = S // n_sp
    mesh = make_mesh({"dp": 2, "sp": n_sp})
    spec = build_registry_spec("transformer_lm", vocab_size=50, hidden=32,
                               num_layers=2, num_heads=4, mlp_dim=64,
                               max_len=S, dropout=0.0)
    lm = model_from_json(spec)
    params = lm.init(jax.random.PRNGKey(0))
    opt = build_optimizer("gradient_descent", 0.1, None)
    step = make_sp_train_step(lm, opt, mesh)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 50, (4, S)), jnp.int32)
    mask = jnp.ones((4, S), jnp.float32)
    p2, _, loss = step(jax.tree.map(jnp.copy, params), opt.init(params), ids,
                       mask, jax.random.PRNGKey(3))

    def ref_loss(p):
        # full-attention logits (ring attention is exact), but the TOKEN loss
        # counts only each shard's local targets 1..Sl-1 (boundary targets
        # between shards excluded, exactly the sp semantics)
        logits = lm.apply(p, {"input_ids": ids, "attention_mask": mask},
                          ["logits"], train=False)["logits"]
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
        w = np.ones((4, S - 1), np.float32)
        for i in range(1, n_sp):
            w[:, i * Sl - 1] = 0.0  # target at a shard boundary
        w = jnp.asarray(w)
        return jnp.sum(nll * w) / jnp.sum(w)

    np.testing.assert_allclose(float(loss), float(ref_loss(params)),
                               rtol=1e-5)
    g = jax.grad(ref_loss)(params)
    sgd = optax.apply_updates(params, jax.tree.map(lambda x: -0.1 * x, g))
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(sgd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


def test_fsdp_training_matches_replicated():
    """ZeRO-style parameter sharding end-to-end: a GSPMD step with
    fsdp-sharded params matches the replicated step's loss and update."""
    import optax
    mesh = make_mesh({"dp": 1, "fsdp": 8})
    spec = build_registry_spec("transformer_classifier", vocab_size=64,
                               num_classes=3, hidden=32, num_layers=2,
                               num_heads=4, mlp_dim=64, max_len=16,
                               dropout=0.0)
    m = model_from_json(spec)
    params = m.init(jax.random.PRNGKey(0))
    fspecs = fsdp_pspecs(m.param_specs(), min_size=32 * 64)
    sharded = shard_params(jax.tree.map(jnp.copy, params), mesh, fspecs)
    assert any("fsdp" in str(l.sharding.spec)
               for l in jax.tree.leaves(sharded))
    opt = build_optimizer("gradient_descent", 0.1, None)
    step = make_sharded_train_step(m, opt, mesh, "input_ids", "y")
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.float32)
    y = jnp.asarray(np.eye(3)[rs.randint(0, 3, 8)], jnp.float32)
    mask = jnp.ones((8,), jnp.float32)
    p2, _, loss = step(sharded, opt.init(sharded), ids, y, mask,
                       jax.random.PRNGKey(1))

    def ref_loss(p):
        return m.loss_vector(p, {"input_ids": ids, "y": y},
                             train=False).mean()

    np.testing.assert_allclose(float(loss), float(ref_loss(params)),
                               rtol=1e-5)
    g = jax.grad(ref_loss)(params)
    sgd = optax.apply_updates(params, jax.tree.map(lambda x: -0.1 * x, g))
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(sgd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)
    # updated params keep their fsdp placement
    assert any("fsdp" in str(l.sharding.spec) for l in jax.tree.leaves(p2))


def test_tp_training_update_exact_vs_single_device():
    """Megatron TP via GSPMD: one tp(4)xdp(2) step equals single-device SGD
    leaf for leaf (the strictest pin, matching the pp/sp/fsdp tests)."""
    import optax
    mesh = make_mesh({"dp": 2, "tp": 4})
    spec = build_registry_spec("transformer_classifier", vocab_size=64,
                               num_classes=3, hidden=32, num_layers=2,
                               num_heads=4, mlp_dim=64, max_len=16,
                               dropout=0.0)
    m = model_from_json(spec)
    params = m.init(jax.random.PRNGKey(0))
    sharded = shard_params(jax.tree.map(jnp.copy, params), mesh,
                           m.param_pspecs())
    opt = build_optimizer("gradient_descent", 0.1, None)
    step = make_sharded_train_step(m, opt, mesh, "input_ids", "y")
    rs = np.random.RandomState(1)
    ids = jnp.asarray(rs.randint(0, 64, (8, 16)), jnp.float32)
    y = jnp.asarray(np.eye(3)[rs.randint(0, 3, 8)], jnp.float32)
    mask = jnp.ones((8,), jnp.float32)
    p2, _, loss = step(sharded, opt.init(sharded), ids, y, mask,
                       jax.random.PRNGKey(1))

    def ref_loss(p):
        return m.loss_vector(p, {"input_ids": ids, "y": y},
                             train=False).mean()

    np.testing.assert_allclose(float(loss), float(ref_loss(params)), rtol=1e-5)
    g = jax.grad(ref_loss)(params)
    sgd = optax.apply_updates(params, jax.tree.map(lambda x: -0.1 * x, g))
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(sgd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-5)


def test_remat_modes_identical_numerics():
    """remat=False / True (full) / 'dots' (save matmul outputs) must give
    identical losses and gradients — remat trades memory for recompute,
    never numerics. Bad mode fails loudly."""
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 50, (4, 16)), jnp.int32)

    results = {}
    for mode in (False, True, "dots"):
        m = model_from_json(build_registry_spec(
            "transformer_lm", vocab_size=50, hidden=32, num_layers=2,
            num_heads=4, mlp_dim=64, max_len=16, dropout=0.0, remat=mode))
        params = m.init(jax.random.PRNGKey(0))

        def loss(p):
            return m.loss_vector(p, {"input_ids": ids}, train=False).mean()

        l, g = jax.value_and_grad(loss)(params)
        results[mode] = (float(l), g)

    l0, g0 = results[False]
    for mode in (True, "dots"):
        l, g = results[mode]
        assert abs(l - l0) < 1e-6, (mode, l, l0)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    with pytest.raises(ValueError, match="remat"):
        model_from_json(build_registry_spec(
            "transformer_lm", vocab_size=50, hidden=32, num_layers=1,
            num_heads=4, mlp_dim=64, max_len=16, remat="everything"))
