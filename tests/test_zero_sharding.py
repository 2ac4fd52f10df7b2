"""ZeRO-2/3 under the declarative ShardingConfig: stage parity, checkpoint
interchange, offload, retrace stability, and the GC-J106 jaxpr gate.

The contract under test (docs/sharding.md): the zero stage changes WHERE
bytes live, never WHAT is computed —

- stages 0-3 produce the same losses/params within reduction-order drift
  (pinned ATOL/RTOL), for every registry optimizer;
- checkpoints always hold the standard layout, so a directory written at
  any stage restores at any other bit-identically;
- ``offload_opt_state`` changes residency only;
- one compile per (stage, shapes): repeated steps never retrace;
- the declared config matches the program's observed collectives (GC-J106
  fires on a planted mismatch, stays silent on every repo-built stage).
"""

import shutil
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkflow_tpu.models.presets import mlp
from sparkflow_tpu.optimizers import AVAILABLE_OPTIMIZERS, build_optimizer
from sparkflow_tpu.optimizers_sharded import (gather_zero3_params,
                                              place_zero1_state,
                                              shard_zero3_params,
                                              sharded_update,
                                              zero3_param_shardings,
                                              zero_memory_report)
from sparkflow_tpu.parallel.dp import make_dp_train_step
from sparkflow_tpu.parallel.mesh import make_mesh, replicate_on_mesh
from sparkflow_tpu.sharding import ShardingConfig, as_sharding_config
from sparkflow_tpu.trainer import Trainer

# reduction-order float drift only: every stage computes the same math
ATOL = 5e-5
RTOL = 1e-5

pytestmark = pytest.mark.skipif(jax.device_count() < 8,
                                reason="needs the 8-virtual-device harness")


def _model():
    from sparkflow_tpu.models import model_from_json
    # hidden=17 -> every weight/bias size is ragged mod 8
    return model_from_json(mlp(10, 3, hidden=(17,)))


def _data(n=64):
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(n, 10), jnp.float32)
    y = jnp.asarray(np.eye(3, dtype=np.float32)[rs.randint(0, 3, n)])
    mask = jnp.ones((n,), jnp.float32)
    return x, y, mask


def _init_for_stage(m, opt, mesh, stage, p0):
    """(params, opt_state) in the layout stage expects, placed on mesh."""
    if stage == 0:
        return jax.tree.map(jnp.array, p0), opt.init(p0)
    state = place_zero1_state(sharded_update(opt, 8, "dp").init(p0), mesh, 8)
    if stage >= 3:
        p = shard_zero3_params(p0, 8)
        p = jax.tree.map(jax.device_put, p, zero3_param_shardings(p, mesh, 8))
        return p, state
    # mesh-placed like the Trainer places them: the step's outputs are, and
    # an unplaced first call would trace a second time
    return replicate_on_mesh(jax.tree.map(jnp.array, p0), mesh), state


def _run_stage(m, opt, mesh, stage, p0, steps=2):
    x, y, mask = _data()
    rng = jax.random.PRNGKey(1)
    step = make_dp_train_step(m, opt, mesh, "x:0", "y:0",
                              sharding=ShardingConfig(zero_stage=stage))
    p, s = _init_for_stage(m, opt, mesh, stage, p0)
    losses = []
    for i in range(steps):
        p, s, l = step(p, s, x, y, mask, jax.random.fold_in(rng, i))
        losses.append(float(l))
    if stage >= 3:
        p = gather_zero3_params(p, p0)
    return losses, p


# -- the config itself ------------------------------------------------------

def test_config_validation_errors():
    with pytest.raises(ValueError, match="zero_stage must be one of"):
        ShardingConfig(zero_stage=5)
    with pytest.raises(ValueError, match="DIFFERENT mesh axis"):
        ShardingConfig(data_axis="dp", dcn_axis="dp")
    mesh = make_mesh({"dp": 8})
    with pytest.raises(ValueError, match="not a mesh axis"):
        ShardingConfig(dcn_axis="dnc").validate(mesh)  # typo'd axis
    # the dp-less message is actionable: names the fix
    pp = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=r"make_mesh\({'dp': N}\)"):
        ShardingConfig(zero_stage=1).validate(pp)


def test_config_dp_less_mesh_falls_back_to_replicated_rows():
    """The ISSUE-1 sharp edge, now through the config path: a mesh without
    the data axis yields replicated rows (P()), not an unknown-axis crash."""
    from jax.sharding import PartitionSpec as P
    pp = make_mesh({"pp": 2}, devices=jax.devices()[:2])
    cfg = ShardingConfig()
    assert cfg.data_spec(pp) == P()
    assert cfg.batch_axes(pp) == ()
    cfg.validate(pp)  # stage 0: fine without a data axis
    assert cfg.data_spec(make_mesh({"dp": 8})) == P("dp")


def test_config_coercion_and_legacy_mapping():
    assert as_sharding_config(None) == ShardingConfig()
    cfg = ShardingConfig(zero_stage=2)
    assert as_sharding_config(cfg) is cfg
    assert as_sharding_config({"zero_stage": 3}).zero_stage == 3
    with pytest.raises(TypeError, match="ShardingConfig"):
        as_sharding_config(3)
    assert ShardingConfig.from_legacy("off").zero_stage == 0
    assert ShardingConfig.from_legacy("auto").zero_stage == 1
    assert ShardingConfig.from_legacy("on").zero_stage == 1
    with pytest.raises(ValueError, match="weight_update_sharding"):
        ShardingConfig.from_legacy("maybe")
    d = ShardingConfig(zero_stage=3, offload_opt_state=True).describe()
    assert d["zero_stage"] == 3 and d["offload_opt_state"] is True


def test_config_model_parallel_axes():
    """tp_axis/ep_axis: orthogonality to the batch axes is enforced at
    construction, typo'd axes at validate(), and the introspection helpers
    report the mesh-resolved degrees."""
    with pytest.raises(ValueError, match="DIFFERENT mesh axis"):
        ShardingConfig(tp_axis="dp")  # collides with data_axis
    with pytest.raises(ValueError, match="DIFFERENT mesh axis"):
        ShardingConfig(dcn_axis="dcn", ep_axis="dcn")
    with pytest.raises(ValueError, match="distinct mesh axes"):
        ShardingConfig(tp_axis="mp", ep_axis="mp")
    with pytest.raises(ValueError, match="non-empty mesh axis"):
        ShardingConfig(tp_axis="")
    with pytest.raises(ValueError, match="tp_axis='tp' is not a mesh axis"):
        ShardingConfig(tp_axis="tp").validate(make_mesh({"dp": 8}))
    mp = make_mesh({"tp": 2, "ep": 4})
    cfg = ShardingConfig(tp_axis="tp", ep_axis="ep").validate(mp)
    assert cfg.tp_size(mp) == 2 and cfg.ep_size(mp) == 4
    assert cfg.model_parallel()
    assert cfg.dp_size(mp) == 1  # dp-less mesh, stage 0: fine
    plain = ShardingConfig()
    assert not plain.model_parallel()
    assert plain.tp_size(mp) == 1 and plain.ep_size(mp) == 1
    d = cfg.describe()
    assert d["tp_axis"] == "tp" and d["ep_axis"] == "ep"
    legacy = ShardingConfig.from_legacy("off", tp_axis="tp", ep_axis="ep")
    assert (legacy.zero_stage, legacy.tp_axis, legacy.ep_axis) == \
        (0, "tp", "ep")


def test_at_rest_leaf_spec_one_rule_two_layouts():
    """docs/sharding.md's claim that fsdp (GSPMD) and flat zero-3 are two
    spellings of ONE per-leaf decision, checked against both consumers."""
    from jax.sharding import PartitionSpec as P

    from sparkflow_tpu.optimizers_sharded import zero1_state_specs
    from sparkflow_tpu.parallel.tp import fsdp_pspecs
    from sparkflow_tpu.sharding import at_rest_leaf_spec

    # gspmd: the LARGEST dim shards, iff the leaf clears min_size
    assert at_rest_leaf_spec((512, 256), "fsdp", layout="gspmd") == \
        P("fsdp", None)
    assert at_rest_leaf_spec((128, 1024), "fsdp", layout="gspmd") == \
        P(None, "fsdp")
    assert at_rest_leaf_spec((17,), "fsdp", layout="gspmd") == P()
    assert at_rest_leaf_spec((4, 4), "fsdp", layout="gspmd",
                             min_size=8) == P("fsdp", None)
    assert at_rest_leaf_spec((), "fsdp", layout="gspmd") == P()
    # flat: dim 0 is shard-bearing by construction ([n_shards, s] leaves)
    assert at_rest_leaf_spec((8, 37), "dp", layout="flat",
                             n_shards=8) == P("dp")
    assert at_rest_leaf_spec((4, 37), "dp", layout="flat",
                             n_shards=8) == P()  # not the flat layout
    assert at_rest_leaf_spec((37,), "dp", layout="flat", n_shards=8) == P()
    with pytest.raises(ValueError, match="'gspmd' or 'flat'"):
        at_rest_leaf_spec((8, 8), "dp", layout="torus")
    # both consumers are pure projections of the rule
    m = _model()
    specs = fsdp_pspecs(m.param_specs(), min_size=64)
    for lname, pspec in m.param_specs().items():
        for pname, (shape, _init) in pspec.items():
            assert specs[lname][pname] == at_rest_leaf_spec(
                shape, "fsdp", layout="gspmd", min_size=64), (lname, pname)
    state = {"mu": jnp.zeros((8, 37)), "count": jnp.zeros(())}
    ss = zero1_state_specs(state, 8)
    assert ss["mu"] == P("dp") and ss["count"] == P()


# -- stage parity, every registry optimizer ---------------------------------

@pytest.mark.parametrize("opt_name", AVAILABLE_OPTIMIZERS)
def test_zero23_match_replicated_all_optimizers(opt_name):
    """Two steps at stages 2 and 3 vs the replicated stage-0 step: same
    losses and params within the pinned reduction-order tolerance, ragged
    param sizes, dp=8."""
    m = _model()
    opt = build_optimizer(opt_name, 1e-2, None)
    mesh = make_mesh({"dp": 8})
    p0 = m.init(jax.random.PRNGKey(0))
    l0, pr0 = _run_stage(m, opt, mesh, 0, p0)
    for stage in (2, 3):
        ls, ps = _run_stage(m, opt, mesh, stage, p0)
        for a, b in zip(l0, ls):
            assert abs(a - b) < ATOL, (opt_name, stage)
        for a, b in zip(jax.tree.leaves(pr0), jax.tree.leaves(ps)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=ATOL, rtol=RTOL,
                                       err_msg=f"{opt_name} stage {stage}")


def test_zero3_param_roundtrip_across_shard_counts():
    """Standard -> flat(8) -> standard -> flat(4) -> standard is exact: the
    flat layout is a pure reshape+pad, so checkpoints written at one dp
    size restore at another bit-for-bit."""
    p0 = _model().init(jax.random.PRNGKey(0))
    f8 = shard_zero3_params(p0, 8)
    assert all(l.shape[0] == 8 for l in jax.tree.leaves(f8))
    back = gather_zero3_params(f8, p0)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    f4 = shard_zero3_params(back, 4)
    back4 = gather_zero3_params(f4, p0)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(back4)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_zero_memory_report_shrinks_with_stage():
    opt = build_optimizer("adam", 1e-2, None)
    p0 = _model().init(jax.random.PRNGKey(0))
    reps = {s: zero_memory_report(opt, p0, 8, s) for s in (0, 1, 2, 3)}
    # stage >=1 shards grads+state at update time; stage 3 also params at rest
    assert reps[1]["grad_opt_at_update"] < reps[0]["grad_opt_at_update"] / 4
    assert reps[2]["grad_opt_at_update"] <= reps[1]["grad_opt_at_update"]
    assert reps[3]["params_at_rest"] < reps[0]["params_at_rest"] / 4
    # within 1.3x of the 1/dp ideal (flat-layout padding is the rest): grads
    # and state at update time under stage 2, params and state at rest under 3
    assert (reps[2]["grad_opt_at_update"]
            <= 1.3 * reps[2]["ideal_grad_opt"])
    assert (reps[3]["params_at_rest"] + reps[3]["opt_state_at_rest"]
            <= 1.3 / 8 * (reps[3]["full_params"] + reps[3]["full_opt_state"]))


# -- trainer integration ----------------------------------------------------

def _fit(sharding, ckpt=None, iters=3, mesh=None, **kw):
    t = Trainer(mlp(10, 3, hidden=(17,)), "x:0", "y:0", optimizer="adam",
                learning_rate=1e-2, mini_batch_size=16, iters=iters, seed=3,
                mesh=mesh if mesh is not None else make_mesh({"dp": 8}),
                sharding=sharding, checkpoint_dir=ckpt,
                checkpoint_every=1 if ckpt else 0, **kw)
    rs = np.random.RandomState(0)
    X = rs.randn(64, 10).astype(np.float32)
    Y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 64)]
    return t, t.fit(X, Y)


def test_trainer_all_stages_agree_and_return_standard_layout():
    runs = {s: _fit(ShardingConfig(zero_stage=s)) for s in (0, 1, 2, 3)}
    base = runs[0][1]
    std_shapes = [l.shape for l in jax.tree.leaves(base.params)]
    for s in (1, 2, 3):
        t, r = runs[s]
        assert t._zero_stage == s
        assert [l.shape for l in jax.tree.leaves(r.params)] == std_shapes
        for a, b in zip(base.losses, r.losses):
            assert abs(a - b) < ATOL, s
        for a, b in zip(jax.tree.leaves(base.params),
                        jax.tree.leaves(r.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("save_stage", [0, 1, 2, 3])
def test_checkpoint_interchange_matrix(save_stage, tmp_path):
    """A checkpoint written at any stage restores at EVERY other stage with
    bit-identical params: checkpoints always hold the standard layout, and
    stage conversion is pure layout (pad/reshape, no arithmetic)."""
    d = str(tmp_path / f"ck{save_stage}")
    t_save, _ = _fit(ShardingConfig(zero_stage=save_stage), ckpt=d, iters=2)
    want = [np.asarray(l) for l in jax.tree.leaves(t_save.params)]
    for restore_stage in (0, 1, 2, 3):
        t_r = Trainer(mlp(10, 3, hidden=(17,)), "x:0", "y:0",
                      optimizer="adam", learning_rate=1e-2,
                      mini_batch_size=16, iters=2, seed=3,
                      mesh=make_mesh({"dp": 8}),
                      sharding=ShardingConfig(zero_stage=restore_stage),
                      checkpoint_dir=d, checkpoint_every=1)
        rs = np.random.RandomState(0)
        X = rs.randn(64, 10).astype(np.float32)
        Y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 64)]
        t_r.fit(X, Y)  # resumes at the final epoch; trains nothing new
        got = [np.asarray(l) for l in jax.tree.leaves(t_r.params)]
        for a, b in zip(want, got):
            assert np.array_equal(a, b), (save_stage, restore_stage)


def test_offload_opt_state_equivalence():
    """offload_opt_state changes residency, not numerics: same losses and
    params as the on-device run, state on host between epochs."""
    t_dev, r_dev = _fit(ShardingConfig(zero_stage=2))
    t_off, r_off = _fit(ShardingConfig(zero_stage=2, offload_opt_state=True))
    assert t_off._offload_active
    for a, b in zip(r_dev.losses, r_off.losses):
        assert abs(a - b) < ATOL
    for a, b in zip(jax.tree.leaves(r_dev.params),
                    jax.tree.leaves(r_off.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=ATOL, rtol=RTOL)
    # the post-fit flush materializes the async host mirror: state ends
    # host-side even though the loop kept a device-resident working copy
    assert all(isinstance(l, np.ndarray)
               for l in jax.tree.leaves(t_off._last_opt_state))


def test_offload_double_buffer_bitwise():
    """The double-buffered offload never round-trips a value through the
    host mid-run (steady-state calls reuse their own device tree; the D2H
    copy is a background mirror), so against an on-device run of the SAME
    per-epoch loop program the losses and final opt state are bitwise
    equal — not merely within float drift."""
    # halt_on_nan forces the on-device arm off the fused multi-epoch
    # program and onto the loop path the offload wrapper uses
    t_dev, r_dev = _fit(ShardingConfig(zero_stage=2), halt_on_nan=True)
    t_off, r_off = _fit(ShardingConfig(zero_stage=2, offload_opt_state=True),
                        halt_on_nan=True)
    assert t_off._offload_active and not t_dev._offload_active
    assert r_dev.losses == r_off.losses
    for a, b in zip(jax.tree.leaves(t_dev._last_opt_state),
                    jax.tree.leaves(t_off._last_opt_state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(r_dev.params),
                    jax.tree.leaves(r_off.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_zero_steps_never_retrace():
    """One trace per stage: repeated steps with fresh data/rng hit the same
    compiled program (RecompileGuard counts traces of the raw stepper)."""
    from sparkflow_tpu.analysis.runtime_guards import RecompileGuard
    m = _model()
    opt = build_optimizer("adam", 1e-2, None)
    mesh = make_mesh({"dp": 8})
    p0 = m.init(jax.random.PRNGKey(0))
    x, y, mask = _data()
    for stage in (2, 3):
        raw = make_dp_train_step(m, opt, mesh, "x:0", "y:0",
                                 sharding=ShardingConfig(zero_stage=stage),
                                 _raw=True)
        guard = RecompileGuard(name=f"zero{stage}")
        step = jax.jit(guard.wrap(raw))
        p, s = _init_for_stage(m, opt, mesh, stage, p0)
        for i in range(3):
            p, s, _ = step(p, s, x + i, y, mask,
                           jax.random.fold_in(jax.random.PRNGKey(7), i))
        assert guard.traces == 1, (stage, guard.report())


def test_trainer_explicit_stage_requests_raise_when_ineligible():
    # dp-less mesh: the config's own actionable message
    with pytest.raises(ValueError, match="zero_stage=2"):
        _fit(ShardingConfig(zero_stage=2), mesh=make_mesh({"fsdp": 8}))
    # blocked optimizer options: shard-local update breaks their math
    with pytest.raises(ValueError, match="clip_norm"):
        _fit(ShardingConfig(zero_stage=2),
             optimizer_options={"clip_norm": 1.0})
    # no mesh at all
    with pytest.raises(ValueError, match="no mesh"):
        t = Trainer(mlp(10, 3), "x:0", "y:0", optimizer="adam",
                    mini_batch_size=16, iters=1,
                    sharding=ShardingConfig(zero_stage=2))
        rs = np.random.RandomState(0)
        t.fit(rs.randn(32, 10).astype(np.float32),
              np.eye(3, dtype=np.float32)[rs.randint(0, 3, 32)])


def test_trainer_dp_less_mesh_with_config_stage0_trains():
    """The dp-less fallback holds through the config path: stage 0 on a
    mesh without 'dp' trains via replicated rows."""
    t, r = _fit(ShardingConfig(zero_stage=0), mesh=make_mesh({"fsdp": 8}))
    assert r.stop_reason == "completed"
    assert np.isfinite(r.losses).all()
    assert t._zero_stage == 0


# -- GC-J106: declared config vs observed collectives ------------------------

def test_gc_j106_repo_stages_lint_clean():
    """The repo gate: every stage the unified builder produces matches its
    own declaration — zero findings, all four stages."""
    from sparkflow_tpu.analysis.jaxpr_lint import lint_dp_train_step
    m = _model()
    mesh = make_mesh({"dp": 8})
    for stage in (0, 1, 2, 3):
        findings = lint_dp_train_step(
            m, "adam", mesh=mesh, sharding=ShardingConfig(zero_stage=stage))
        assert findings == [], (stage, findings)


def test_gc_j106_planted_mismatch_both_directions():
    from sparkflow_tpu.analysis.jaxpr_lint import lint_sharding_config
    m = _model()
    opt = build_optimizer("adam", 1e-2, None)
    mesh = make_mesh({"dp": 8})
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((8, 10), np.float32)
    y = jax.ShapeDtypeStruct((8, 3), np.float32)
    mask = jax.ShapeDtypeStruct((8,), np.float32)
    rng = jax.random.PRNGKey(0)

    # a stage-0 program declared as stage 2: no reduce_scatter -> finding
    step0 = make_dp_train_step(m, opt, mesh, "x:0", "y:0",
                               sharding=ShardingConfig(zero_stage=0),
                               _raw=True)
    s0 = jax.eval_shape(opt.init, p)
    found = lint_sharding_config(step0, (p, s0, x, y, mask, rng),
                                 ShardingConfig(zero_stage=2))
    assert len(found) == 1 and found[0].rule == "GC-J106"
    assert "reduce_scatter" in found[0].message

    # a stage-2 program declared as stage 0: scatter machinery -> finding
    step2 = make_dp_train_step(m, opt, mesh, "x:0", "y:0",
                               sharding=ShardingConfig(zero_stage=2),
                               _raw=True)
    s2 = jax.eval_shape(sharded_update(opt, 8, "dp").init, p)
    found = lint_sharding_config(step2, (p, s2, x, y, mask, rng),
                                 ShardingConfig(zero_stage=0))
    assert len(found) == 1 and found[0].rule == "GC-J106"
    # suppression works like every other rule
    assert lint_sharding_config(step2, (p, s2, x, y, mask, rng),
                                ShardingConfig(zero_stage=0),
                                ignore=("GC-J106",)) == []


# -- serving consumes the same config ----------------------------------------

def test_inference_engine_accepts_sharding_config():
    from sparkflow_tpu.serving.engine import InferenceEngine
    t, r = _fit(ShardingConfig(zero_stage=3))
    eng = InferenceEngine(mlp(10, 3, hidden=(17,)), r.params,
                          mesh=make_mesh({"dp": 8}),
                          sharding=ShardingConfig(zero_stage=3),
                          max_batch=16, warmup=False)
    out = eng.predict(np.random.RandomState(1).randn(16, 10)
                      .astype(np.float32))
    assert out.shape == (16, 3) and np.isfinite(out).all()
    assert eng.stats()["sharding"]["zero_stage"] == 3
    with pytest.raises(ValueError, match="not a mesh axis"):
        InferenceEngine(mlp(10, 3, hidden=(17,)), r.params,
                        mesh=make_mesh({"dp": 8}),
                        sharding=ShardingConfig(dcn_axis="oops"),
                        warmup=False)
