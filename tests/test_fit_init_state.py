"""The state a ``Trainer.fit`` starts from (``Trainer._fresh_state``): its own
copy of ``init_params`` and the optimizer's fresh state come from ONE device
program over the whole tree, the caller's arrays stay the caller's, the last
fit's optimizer state is let go before the new one is made, and on a mesh
params and state rest where the eager construction left them."""

import gc
import glob
import os
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import sparkflow_tpu.nn as nn
from sparkflow_tpu.graph_utils import build_graph
from sparkflow_tpu.parallel.mesh import make_mesh, replicate_on_mesh
from sparkflow_tpu.trainer import Trainer
from sparkflow_tpu.utils import tracing

OPTIMIZERS = {"adam": {}, "adam_ema": {"ema_decay": 0.9}}


def dense_graph(layers=2):
    def graph():
        x = nn.placeholder([None, 10], name="x")
        y = nn.placeholder([None, 2], name="y")
        h = x
        for _ in range(layers - 1):
            h = nn.dense(h, 16, activation="relu")
        out = nn.dense(h, 2, name="out")
        nn.softmax_cross_entropy(y, out)
    return build_graph(graph)


def clf_data(seed=0, n=96):
    rs = np.random.RandomState(seed)
    return (rs.randn(n, 10).astype(np.float32),
            np.eye(2, dtype=np.float32)[rs.randint(0, 2, n)])


def trainer(layers=2, opt="adam", **kw):
    return Trainer(dense_graph(layers), "x:0", "y:0", iters=3,
                   mini_batch_size=32, optimizer="adam",
                   optimizer_options=dict(learning_rate=0.01,
                                          **OPTIMIZERS[opt]), **kw)


def eager_state(self, tree, *, with_opt, replicate=False):
    """``_fresh_state`` as the fit built it before: ``jnp.array`` a leaf, the
    optimizer's eager ``init``, each placed on the mesh afterwards."""
    place = ((lambda t: replicate_on_mesh(t, self.mesh)) if replicate
             else (lambda t: t))
    params = place(jax.tree.map(lambda a: jnp.array(a), tree))
    if not with_opt:
        return params
    return params, place(self.optimizer.init(params))


def buffers(tree):
    return {shard.data.unsafe_buffer_pointer()
            for leaf in jax.tree.leaves(tree)
            for shard in leaf.addressable_shards}


# -- (a) the caller's arrays stay the caller's --------------------------------


@pytest.mark.parametrize("source", ["seeded", "numpy", "own_params"])
def test_fit_leaves_the_callers_params_alive_and_unshared(source):
    X, Y = clf_data()
    tr = trainer()
    if source == "own_params":
        first = tr.fit(X, Y)
        p = tr.params
        assert p is first.params
    else:
        p = tr.model.init(jax.random.PRNGKey(3))
        if source == "numpy":
            p = jax.tree.map(np.asarray, p)
    before = jax.tree.map(lambda a: np.array(a), p)
    results = [tr.fit(X, Y, init_params=p) for _ in range(2)]
    for leaf, was in zip(jax.tree.leaves(p), jax.tree.leaves(before)):
        assert not getattr(leaf, "is_deleted", lambda: False)()
        np.testing.assert_array_equal(np.asarray(leaf), was)
    if source != "numpy":
        assert not buffers(p) & buffers(tr.params)
    # every earlier result stays readable, and is no later one's memory
    assert not buffers(results[0].params) & buffers(results[1].params)
    for res in results:
        assert all(np.isfinite(np.asarray(leaf)).all()
                   for leaf in jax.tree.leaves(res.params))
    for a, b in zip(jax.tree.leaves(results[0].params),
                    jax.tree.leaves(results[1].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- (b) numerics: a copy and zeros are exact ---------------------------------


def two_fits(tr):
    X, Y = clf_data()
    r1 = tr.fit(X, Y)
    ema1 = tr.ema_weights()
    r2 = tr.fit(X, Y, init_params=tr.params)
    return ([r1.losses, r2.losses],
            jax.tree.map(np.asarray, [r1.params, r2.params, ema1,
                                      tr.ema_weights()]))


@pytest.mark.parametrize("opt", sorted(OPTIMIZERS))
def test_two_fits_are_bit_identical_to_the_eager_construction(opt,
                                                              monkeypatch):
    losses, trees = two_fits(trainer(opt=opt))
    monkeypatch.setattr(Trainer, "_fresh_state", eager_state)
    old_losses, old_trees = two_fits(trainer(opt=opt))
    assert losses == old_losses
    assert (jax.tree.structure(trees) == jax.tree.structure(old_trees))
    for a, b in zip(jax.tree.leaves(trees), jax.tree.leaves(old_trees)):
        np.testing.assert_array_equal(a, b)
    # the ema chain carries its average; plain adam has none to serve
    assert (trees[3] is not None) == (opt == "adam_ema")


# -- (c) one program, however many leaves -------------------------------------


def programs_in_init_state(layers, tmp_path):
    """Executions the runtime records inside the ``train/init_state``
    annotation of a CPU profile of a second fit."""
    X, Y = clf_data()
    tr = trainer(layers)
    tr.fit(X, Y)                        # compile outside the capture
    with tracing.trace(str(tmp_path)):
        tr.fit(X, Y, init_params=tr.params)
    xplane, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in jax.profiler.ProfileData.from_file(xplane).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events]
    (t0, t1), = [(a, b) for name, a, b in events
                 if name == "train/init_state"]
    runs = [name for name, a, b in events
            if name.endswith("Executable::Execute") and t0 <= a and b <= t1]
    return len(runs), len(jax.tree.leaves(tr.params))


def test_init_state_programs_do_not_grow_with_the_tree(tmp_path):
    few, leaves_few = programs_in_init_state(2, tmp_path / "two")
    many, leaves_many = programs_in_init_state(4, tmp_path / "four")
    assert leaves_many >= 2 * leaves_few
    # the key's seed and split, and the one program for params and state
    assert 1 <= few <= 8
    assert many == few


# -- (d) the last fit's state goes before the new one is made -----------------


def test_a_fit_lets_go_of_the_last_fits_optimizer_state(monkeypatch):
    X, Y = clf_data()
    tr = trainer(opt="adam_ema")
    tr.fit(X, Y)
    assert tr.ema_weights() is not None
    old = weakref.ref(jax.tree.leaves(tr._last_opt_state)[-1])
    seen = []
    fresh_state = Trainer._fresh_state

    def spy(self, tree, **kw):
        gc.collect()
        seen.append((self._last_opt_state, old(), self.ema_weights()))
        return fresh_state(self, tree, **kw)

    monkeypatch.setattr(Trainer, "_fresh_state", spy)
    tr.fit(X, Y, init_params=tr.params)
    # already gone when the new state is made, not only after the fit
    assert seen == [(None, None, None)]
    gc.collect()
    assert old() is None
    assert tr.ema_weights() is not None


def test_a_refused_fit_keeps_the_state_and_a_failed_one_does_not(monkeypatch):
    X, Y = clf_data()
    tr = trainer(opt="adam_ema")
    tr.fit(X, Y)
    with pytest.raises(ValueError, match="no training data"):
        tr.fit(X[:0], Y[:0])            # refused in train/plan
    assert tr.ema_weights() is not None

    def boom(self, tree, **kw):
        raise RuntimeError("while the state is made")

    monkeypatch.setattr(Trainer, "_fresh_state", boom)
    with pytest.raises(RuntimeError, match="while the state is made"):
        tr.fit(X, Y, init_params=tr.params)
    assert tr.ema_weights() is None


# -- (e) on a mesh: the same placement, and no second trace -------------------


def dp_trainer():
    return trainer(mesh=make_mesh({"dp": 8}), debug_recompiles=True,
                   weight_update_sharding="off")


def tp_trainer():
    tr = trainer(mesh=make_mesh({"dp": 2, "tp": 4}), debug_recompiles=True)
    specs = {layer: {name: (P(None, "tp") if leaf.ndim == 2 and
                            leaf.shape[1] % 4 == 0 else P())
                     for name, leaf in group.items()}
             for layer, group in tr.model.init(jax.random.PRNGKey(0)).items()}
    assert any(s != P() for g in specs.values() for s in g.values())
    tr.param_sharding = specs
    return tr


def handed_to_the_epoch_program(tr, X, Y):
    """(params, opt_state) as the fit's second call hands them to the
    compiled epoch program, and that fit's recompile report."""
    tr.fit(X, Y)
    (key, prog), = tr._epoch_cache.items()
    got = []

    def recording(params, opt_state, *rest):
        got.append((jax.tree.structure((params, opt_state)),
                    [(a.shape, a.dtype, a.sharding)
                     for a in jax.tree.leaves((params, opt_state))]))
        return prog(params, opt_state, *rest)

    tr._epoch_cache[key] = recording
    tr.fit(X, Y, init_params=tr.params)
    handed, = got
    return handed, tr.recompile_report


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs the 8-virtual-device harness")
@pytest.mark.parametrize("make", [dp_trainer, tp_trainer])
def test_mesh_fit_places_state_as_before_and_does_not_trace_again(
        make, monkeypatch):
    X, Y = clf_data()
    (tree, new), report = handed_to_the_epoch_program(make(), X, Y)
    assert "trace(s)" not in report     # the second fit traced nothing
    monkeypatch.setattr(Trainer, "_fresh_state", eager_state)
    (old_tree, old), _ = handed_to_the_epoch_program(make(), X, Y)
    assert tree == old_tree and len(new) == len(old)
    for (shape, dtype, placed), (old_shape, old_dtype, old_placed) in zip(
            new, old):
        assert (shape, dtype) == (old_shape, old_dtype)
        assert placed.is_equivalent_to(old_placed, len(shape))


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs the 8-virtual-device harness")
@pytest.mark.parametrize("target", ["smaller_mesh", "no_mesh"])
def test_init_params_committed_to_another_mesh_are_taken(target):
    """The pinned program cannot mix device sets: arrays another trainer left
    on its own mesh move to this one's first, as ``replicate_on_mesh`` after
    the eager copy used to move them."""
    X, Y = clf_data()
    first = dp_trainer()
    p = first.fit(X, Y).params
    kw = ({} if target == "no_mesh" else dict(
        mesh=make_mesh({"dp": 4}, devices=jax.devices()[:4]),
        weight_update_sharding="off"))
    second = trainer(**kw)
    res = second.fit(X, Y, init_params=p)
    assert np.isfinite(res.losses).all()
    assert not buffers(p) & buffers(second.params)
    assert all(not leaf.is_deleted() for leaf in jax.tree.leaves(p))
