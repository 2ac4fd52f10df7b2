"""What the program names inside itself for a profile: the host phases of a
``Trainer.fit`` as spans (on the active tracer, and as annotations on a
running JAX profile's clock), on the fused path too; and the scopes of a train
step's phases in the lowered program. ``tests/test_tpu_compile.py`` holds the
kernels' names."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import sparkflow_tpu.nn as nn
from sparkflow_tpu.core import _step_body, make_loss_fn
from sparkflow_tpu.graph_utils import build_graph
from sparkflow_tpu.models import build_registry_spec, model_from_json
from sparkflow_tpu.obs import Tracer, default_tracer, phases
from sparkflow_tpu.trainer import Trainer
from sparkflow_tpu.utils import tracing

ROOT = "train/fit"
CHILDREN = ["train/plan", "train/init_state", "train/transfer",
            "train/launch", "train/wait", "train/finish"]


def clf_graph():
    x = nn.placeholder([None, 10], name="x")
    y = nn.placeholder([None, 2], name="y")
    h = nn.dense(x, 16, activation="relu")
    out = nn.dense(h, 2, name="out")
    nn.softmax_cross_entropy(y, out)


def clf_data(seed=0, n=96):
    rs = np.random.RandomState(seed)
    return (rs.randn(n, 10).astype(np.float32),
            np.eye(2, dtype=np.float32)[rs.randint(0, 2, n)])


# -- the phases helper --------------------------------------------------------


def test_phases_open_one_child_at_a_time():
    t = Tracer()
    with t.activate(), phases("root") as ph:
        ph.enter("a")
        ph.enter("b")          # closes a
        ph.leave()
        ph.leave()             # nothing open: a no-op
        ph.enter("c")          # closed with the root
    root, = [s for s in t.spans() if s.name == "root"]
    kids = [s for s in t.spans() if s.name != "root"]
    assert [s.name for s in kids] == ["a", "b", "c"]
    assert all(s.parent_id == root.span_id for s in kids)
    assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
    assert root.t0 <= kids[0].t0 and kids[-1].t1 <= root.t1
    assert t.current() is None


def test_phases_close_on_an_exception():
    t = Tracer()
    with pytest.raises(RuntimeError):
        with t.activate(), phases("root") as ph:
            ph.enter("a")
            raise RuntimeError("in a phase")
    assert sorted(s.name for s in t.spans()) == ["a", "root"]
    assert all(s.t1 is not None for s in t.spans()) and t.current() is None


# -- a fused fit, without trace_spans, under a tracer and a JAX profile -------


@pytest.fixture(scope="module")
def fused_fit(tmp_path_factory):
    X, Y = clf_data()
    tr = Trainer(build_graph(clf_graph), "x:0", "y:0", iters=3,
                 mini_batch_size=32)
    tr.fit(X, Y)                        # compile outside the capture
    log_dir = str(tmp_path_factory.mktemp("profile"))
    tracer = Tracer()
    with tracing.trace(log_dir), tracer.activate():
        res = tr.fit(X, Y, init_params=tr.params)
    xplane, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(xplane)
    host = {}
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return tr, res, tracer.spans(), host


def test_untraced_fit_goes_the_fused_way(fused_fit):
    tr, res, spans, _ = fused_fit
    assert len(res.losses) == 3
    assert [k[0] for k in tr._epoch_cache] == ["fused"]
    assert tr.last_step_stats is None and tr.last_trace_path is None
    assert sorted(s.name for s in spans) == sorted([ROOT] + CHILDREN)


@pytest.mark.parametrize("name", [ROOT] + CHILDREN)
def test_fused_fit_records_its_host_phases(fused_fit, name):
    """Once each; the children parented to ``train/fit``, inside it, disjoint
    and in order, summing to no more than it."""
    _, _, spans, _ = fused_fit
    sp, = [s for s in spans if s.name == name]
    root, = [s for s in spans if s.name == ROOT]
    assert sp.t1 is not None and sp.t1 >= sp.t0
    if name == ROOT:
        assert sp.parent_id is None
        kids = [s for s in spans if s.name != ROOT]
        assert sum(s.duration_s for s in kids) <= sp.duration_s
        return
    assert sp.parent_id == root.span_id
    assert root.t0 <= sp.t0 and sp.t1 <= root.t1
    i = CHILDREN.index(name)
    if i:
        before, = [s for s in spans if s.name == CHILDREN[i - 1]]
        assert before.t1 <= sp.t0


@pytest.mark.parametrize("name", [ROOT] + CHILDREN)
def test_fused_fit_annotates_a_running_profile(fused_fit, name):
    """The same phases lie in the profile's host plane, on the clock the
    device's events are on, with the Python tracer off
    (``utils.tracing.trace``'s default)."""
    _, _, _, host = fused_fit
    (start, end), = host[name]
    root, = host[ROOT]
    assert root[0] <= start <= end <= root[1]
    # the Python tracer's events are named "$file:line function"
    assert not any(n.startswith("$") for n in host)


def test_unobserved_fit_records_to_the_default_tracer():
    """With no tracer activated a fit's seven spans go to the bounded
    default ring, as every module-level span does."""
    X, Y = clf_data(1, 64)
    tr = Trainer(build_graph(clf_graph), "x:0", "y:0", iters=2,
                 mini_batch_size=64)
    default_tracer.clear()
    tr.fit(X, Y)
    assert sorted(s.name for s in default_tracer.spans()) == sorted(
        [ROOT] + CHILDREN)


def test_traced_fit_keeps_the_loop_path_and_gains_the_phases(tmp_path):
    """``trace_spans`` still takes the per-epoch path; its root and its
    ``train/transfer`` come from the same one site as the fused path's."""
    X, Y = clf_data(2)
    tr = Trainer(build_graph(clf_graph), "x:0", "y:0", iters=3,
                 mini_batch_size=96)
    tr.fit(X, Y, trace_spans=str(tmp_path / "trace.json"))
    assert not any(k[0] == "fused" for k in tr._epoch_cache)
    spans = tr.last_tracer.spans()
    count = lambda name: sum(s.name == name for s in spans)
    assert count(ROOT) == count("train/transfer") == 1
    assert count("train/plan") == count("train/init_state") == 1
    assert count("train/wait") == count("train/finish") == 1
    assert count("train/launch") == 3       # one per epoch_fn call
    assert count("train/step") + count("train/step_compile") == 3
    s = tr.last_step_stats
    assert s["phase_counts"]["transfer"] == 1 and s["steps"] == 3
    root, = [sp for sp in spans if sp.name == ROOT]
    assert all(sp.parent_id == root.span_id for sp in spans
               if sp.name.startswith("train/") and sp is not root)


# -- the scopes of a train step -----------------------------------------------


@pytest.fixture(scope="module")
def step_paths():
    """Every op's path in a tiny transformer train step, lowered on the
    CPU with debug info."""
    model = model_from_json(build_registry_spec(
        "transformer_lm", dropout=0.0, vocab_size=64, hidden=32,
        num_layers=2, num_heads=2, mlp_dim=64, max_len=16))
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    step = _step_body(make_loss_fn(model, "input_ids", None), opt)
    lowered = jax.jit(step).lower(
        params, opt.init(params), jnp.zeros((4, 16), jnp.float32),
        jnp.zeros((4, 1), jnp.float32), jnp.ones((4,), jnp.float32),
        jax.random.PRNGKey(1))
    return set(re.findall(r'loc\("(jit\(step\)/[^"]+)"',
                          lowered.as_text(debug_info=True)))


@pytest.mark.parametrize("what, pattern", [
    ("optimizer", r"^jit\(step\)/optimizer/"),
    ("forward", r"^jit\(step\)/loss/(?!.*transpose\()"),
    ("backward", r"^jit\(step\)/loss/.*transpose\("),
    ("embed", r"/loss/jvp\(embed\)/"),
    ("attention forward", r"/loss/jvp\(attention\)/"),
    ("attention backward", r"/loss/transpose\(jvp\(attention\)\)/"),
    ("mlp forward", r"/loss/jvp\(mlp\)/"),
    ("mlp backward", r"/loss/transpose\(jvp\(mlp\)\)/"),
    ("lm_head", r"/loss/jvp\(lm_head\)/"),
])
def test_train_step_carries_its_scopes(step_paths, what, pattern):
    assert any(re.search(pattern, p) for p in step_paths), (
        what, sorted(step_paths)[:20])


def test_every_op_of_the_step_lies_under_a_phase(step_paths):
    """Nothing of a step is outside ``loss`` and ``optimizer``: a profile's
    forward / backward / optimizer split leaves no remainder."""
    outside = [p for p in step_paths
               if not re.match(r"jit\(step\)/(loss|optimizer)(/|$)", p)]
    assert not outside, outside[:10]
