"""Live weight publication: versioned store, hot swap, canary gate, chaos.

Covers the PR's acceptance criteria directly: crash-consistent publish
(a torn publish — crash between manifest and rename — is invisible to
readers), checksum-verified loads with automatic fallback past corrupt
versions, rollback quarantine, watcher-driven hot swap that is bitwise
identical to a cold start with zero retraces, the DecodeEngine's deferred
token-boundary swap, the canary health gate (error-rate / NaN / latency)
with store rollback, the Trainer/ElasticParamStore ``publish_to`` hooks,
and the static gates (GC-L301/302/303 lock lint, lock-order graph, GC-R402
lockset race check) over the new code.
"""

import json
import os
import shutil
import threading

import numpy as np
import pytest

import jax

import sparkflow_tpu.nn as nn
from sparkflow_tpu.analysis import lockgraph, locks, racecheck
from sparkflow_tpu.graph_utils import build_graph
from sparkflow_tpu.models.registry import build_registry_spec, model_from_json
from sparkflow_tpu.resilience import faults
from sparkflow_tpu.serving import (CanaryController, ContinuousBatcher,
                                   DecodeEngine, InferenceEngine, WeightStore,
                                   WeightStoreError, WeightWatcher)
from sparkflow_tpu.serving.membership import Replica
from sparkflow_tpu.trainer import Trainer
from sparkflow_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

IN, OUT = "x:0", "out/BiasAdd:0"


def mlp_graph():
    x = nn.placeholder([None, 4], name="x")
    h = nn.dense(x, 3, activation="relu")
    out = nn.dense(h, 2, name="out")
    nn.mean_squared_error(x, out)


@pytest.fixture(scope="module")
def graph_json():
    return build_graph(mlp_graph)


def _mlp_weights(seed):
    rs = np.random.RandomState(seed)
    return [rs.randn(4, 3).astype(np.float32),
            rs.randn(3).astype(np.float32),
            rs.randn(3, 2).astype(np.float32),
            rs.randn(2).astype(np.float32)]


def _mlp_tree(graph_json, seed):
    """The canonical params pytree for the MLP graph — the standard layout
    a trainer publishes (a flat list's leaf order differs from the tree's
    sorted order, so stores feeding engine templates publish trees)."""
    from sparkflow_tpu.graphdef import list_to_params
    from sparkflow_tpu.models import model_from_json
    return list_to_params(model_from_json(graph_json), _mlp_weights(seed))


def _bitwise(a, b):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(fa) == len(fb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(fa, fb))


# -- store: publish / load / verify ------------------------------------------


def test_publish_load_roundtrip(tmp_path):
    store = WeightStore(str(tmp_path))
    w1, w2 = _mlp_weights(0), _mlp_weights(1)
    assert store.publish(w1) == 1
    assert store.publish(w2) == 2
    assert store.all_versions() == [1, 2]
    assert store.latest_version() == 2
    v, got = store.load(like=w2)
    assert v == 2 and _bitwise(got, w2)
    v, got = store.load(version=1, like=w1)
    assert v == 1 and _bitwise(got, w1)
    assert store.verify_version(1) and store.verify_version(2)


def test_empty_store_loads_none(tmp_path):
    store = WeightStore(str(tmp_path))
    assert store.load() is None
    assert store.latest_version() is None


def test_version_regression_raises(tmp_path):
    store = WeightStore(str(tmp_path))
    store.publish(_mlp_weights(0), version=5)
    with pytest.raises(WeightStoreError, match="monotone"):
        store.publish(_mlp_weights(1), version=3)
    with pytest.raises(WeightStoreError, match="monotone"):
        store.publish(_mlp_weights(1), version=5)  # republish is not a thing
    assert store.publish(_mlp_weights(1)) == 6  # auto continues past it
    assert store.latest_version() == 6


def test_shape_drift_rejected_at_load(tmp_path):
    # the shapes-unchanged contract: a published tree that drifts in shape
    # must fail the template check, not be discovered as a retrace
    store = WeightStore(str(tmp_path))
    store.publish(_mlp_weights(0))
    bad_template = _mlp_weights(0)
    bad_template[0] = np.zeros((5, 3), np.float32)
    with pytest.raises(WeightStoreError, match="shapes must be unchanged"):
        store.load(version=1, like=bad_template)


def test_gc_keeps_newest(tmp_path):
    store = WeightStore(str(tmp_path), keep=2)
    for s in range(4):
        store.publish(_mlp_weights(s))
    assert store.all_versions() == [3, 4]
    assert store.load(like=_mlp_weights(0))[0] == 4


# -- store: chaos battery -----------------------------------------------------


def test_torn_publish_invisible(tmp_path):
    """Crash in the window between manifest write and the atomic rename:
    the pointer stays on the previous version and no reader ever sees a
    half-written v_<n>."""
    store = WeightStore(str(tmp_path))
    w1 = _mlp_weights(0)
    store.publish(w1)
    with faults.inject("weights.publish_commit", fail_calls=[0]):
        with pytest.raises(faults.InjectedFault):
            store.publish(_mlp_weights(1))
    assert store.all_versions() == [1]
    assert store.latest_version() == 1
    v, got = store.load(like=w1)
    assert v == 1 and _bitwise(got, w1)
    # and the next publish proceeds cleanly onto version 2
    assert store.publish(_mlp_weights(2)) == 2


def test_sigkill_tmp_dir_never_read(tmp_path):
    """A SIGKILL mid-publish (no exception handler runs) leaves a _tmp_*
    dir behind; readers never mistake it for a version and the next
    publisher is unaffected."""
    store = WeightStore(str(tmp_path))
    store.publish(_mlp_weights(0))
    leftover = os.path.join(str(tmp_path), "_tmp_v2_99999")
    os.makedirs(leftover)
    with open(os.path.join(leftover, "weights.npz"), "wb") as f:
        f.write(b"partial garbage")
    assert store.all_versions() == [1]
    assert store.latest_version() == 1
    assert store.publish(_mlp_weights(1)) == 2


def test_corrupt_weight_file_falls_back(tmp_path):
    """Bit-rot in the newest version's weights: verification fails and the
    default load falls back to the newest verifiable version; an explicit
    load of the corrupt version raises."""
    store = WeightStore(str(tmp_path))
    w1 = _mlp_weights(0)
    store.publish(w1)
    store.publish(_mlp_weights(1))
    faults.corrupt_latest_weights(str(tmp_path), mode="flip")
    assert not store.verify_version(2)
    v, got = store.load(like=w1)
    assert v == 1 and _bitwise(got, w1)
    with pytest.raises(WeightStoreError, match="torn or corrupt"):
        store.load(version=2, like=w1)


def test_truncated_manifest_falls_back(tmp_path):
    store = WeightStore(str(tmp_path))
    store.publish(_mlp_weights(0))
    store.publish(_mlp_weights(1))
    faults.corrupt_latest_weights(str(tmp_path), mode="manifest")
    assert not store.verify_version(2)
    assert store.load(like=_mlp_weights(0))[0] == 1


def test_torn_latest_json_pointer_scans_dirs(tmp_path):
    """An unreadable latest.json is only a pointer loss: discovery falls
    back to scanning version dirs and still serves the newest one."""
    store = WeightStore(str(tmp_path))
    store.publish(_mlp_weights(0))
    store.publish(_mlp_weights(1))
    faults.corrupt_latest_weights(str(tmp_path), mode="latest_json")
    assert store.latest_version() == 2
    assert store.load(like=_mlp_weights(0))[0] == 2


def test_restart_onto_newest_verifiable(tmp_path):
    """The replica-restart path: a FRESH store handle (new process) over a
    directory whose newest version is corrupt starts on the newest
    verifiable one, skipping the bad version by checksum alone."""
    store = WeightStore(str(tmp_path))
    w2 = _mlp_weights(1)
    store.publish(_mlp_weights(0))
    store.publish(w2)
    store.publish(_mlp_weights(2))
    faults.corrupt_latest_weights(str(tmp_path), mode="flip")  # damages v3
    fresh = WeightStore(str(tmp_path))
    v, got = fresh.load(like=w2)
    assert v == 2 and _bitwise(got, w2)


def test_rollback_quarantines_version(tmp_path):
    store = WeightStore(str(tmp_path))
    w1 = _mlp_weights(0)
    store.publish(w1)
    store.publish(_mlp_weights(1))
    assert store.rollback(bad_version=2) == 1
    assert store.latest_version() == 1
    assert store.quarantined() == {2}
    # v2 is intact on disk but never offered again, even by fallback
    v, got = store.load(like=w1)
    assert v == 1 and _bitwise(got, w1)
    # the next publish moves PAST the quarantined number (monotone)
    assert store.publish(_mlp_weights(2)) == 3
    assert store.load(like=w1)[0] == 3


def test_rollback_with_nothing_good_left(tmp_path):
    store = WeightStore(str(tmp_path))
    store.publish(_mlp_weights(0))
    assert store.rollback(bad_version=1) is None
    assert store.latest_version() is None


def test_all_versions_corrupt_raises(tmp_path):
    store = WeightStore(str(tmp_path))
    store.publish(_mlp_weights(0))
    faults.corrupt_latest_weights(str(tmp_path), mode="flip")
    with pytest.raises(WeightStoreError, match="no loadable weights"):
        store.load(like=_mlp_weights(0))


# -- InferenceEngine hot swap -------------------------------------------------


def test_engine_swap_parity_and_zero_retrace(graph_json):
    """The swapped engine's predictions are bitwise those of an engine
    cold-started on the new weights, with zero steady-state retraces and
    zero fallback compiles — the AOT executables are reused as-is."""
    w_old, w_new = _mlp_weights(0), _mlp_weights(7)
    eng = InferenceEngine(graph_json, w_old, input_name=IN, output_name=OUT,
                          max_batch=8)
    cold = InferenceEngine(graph_json, w_new, input_name=IN, output_name=OUT,
                           max_batch=8)
    x = np.random.RandomState(3).randn(5, 4).astype(np.float32)
    eng.predict(x)  # old weights serving
    assert eng.swap_params(w_new, version=1) is True
    assert eng.serving_version() == 1
    np.testing.assert_array_equal(np.asarray(eng.predict(x)),
                                  np.asarray(cold.predict(x)))
    st = eng.stats()
    assert st["swaps"] == 1 and st["serving_version"] == 1
    assert st["steady_traces"] == 0 and st["fallback_compiles"] == 0


def test_engine_swap_shape_mismatch_rejected(graph_json):
    eng = InferenceEngine(graph_json, _mlp_weights(0), input_name=IN,
                          output_name=OUT, max_batch=4)
    bad = _mlp_weights(1)
    bad[2] = np.zeros((3, 5), np.float32)  # widened output layer
    with pytest.raises(Exception):  # shape validation (engine or loader)
        eng.swap_params(bad)
    assert eng.serving_version() == 0  # still on ctor weights


def test_engine_swap_fault_keeps_last_good(graph_json):
    eng = InferenceEngine(graph_json, _mlp_weights(0), input_name=IN,
                          output_name=OUT, max_batch=4)
    with faults.inject("engine.swap", fail_calls=[0]):
        with pytest.raises(faults.InjectedFault):
            eng.swap_params(_mlp_weights(1))
    assert eng.serving_version() == 0
    x = np.zeros((2, 4), np.float32)
    assert np.isfinite(np.asarray(eng.predict(x))).all()


# -- WeightWatcher ------------------------------------------------------------


def test_watcher_swaps_on_publish(graph_json, tmp_path):
    store = WeightStore(str(tmp_path))
    eng = InferenceEngine(graph_json, _mlp_weights(0), input_name=IN,
                          output_name=OUT, max_batch=4)
    cold = InferenceEngine(graph_json, _mlp_weights(9), input_name=IN,
                           output_name=OUT, max_batch=4)
    watcher = WeightWatcher(store, [eng], poll_interval_s=0.01)
    assert watcher.poll_once() is False  # nothing published yet
    store.publish(_mlp_tree(graph_json, 9))
    assert watcher.poll_once() is True
    assert watcher.serving_version() == 1
    x = np.random.RandomState(0).randn(3, 4).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(eng.predict(x)),
                                  np.asarray(cold.predict(x)))
    # idempotent: the same version is not re-pulled
    assert watcher.poll_once() is False
    assert watcher.stats()["swaps"] == 1


def test_watcher_keeps_last_good_on_corrupt_publish(graph_json, tmp_path):
    """A corrupt publish is a counter and a log line on the replica —
    never a serving error. The next good publish swaps normally."""
    store = WeightStore(str(tmp_path))
    eng = InferenceEngine(graph_json, _mlp_weights(0), input_name=IN,
                          output_name=OUT, max_batch=4)
    watcher = WeightWatcher(store, [eng], poll_interval_s=0.01)
    store.publish(_mlp_tree(graph_json, 1))
    assert watcher.poll_once() is True and eng.serving_version() == 1
    store.publish(_mlp_tree(graph_json, 2))
    faults.corrupt_latest_weights(str(tmp_path), mode="flip")  # damages v2
    assert watcher.poll_once() is False
    st = watcher.stats()
    assert st["pull_failures"] == 1 and st["failed_versions"] == [2]
    assert eng.serving_version() == 1  # last-good kept
    x = np.zeros((2, 4), np.float32)
    assert np.isfinite(np.asarray(eng.predict(x))).all()
    store.publish(_mlp_tree(graph_json, 3))  # v3, good
    assert watcher.poll_once() is True
    assert eng.serving_version() == 3


def test_watcher_follows_rollback_down(graph_json, tmp_path):
    """Rollback is just a pointer move to a LOWER version: watchers follow
    it and replicas revert."""
    store = WeightStore(str(tmp_path))
    eng = InferenceEngine(graph_json, _mlp_weights(0), input_name=IN,
                          output_name=OUT, max_batch=4)
    watcher = WeightWatcher(store, [eng], poll_interval_s=0.01)
    store.publish(_mlp_tree(graph_json, 1))
    store.publish(_mlp_tree(graph_json, 2))
    assert watcher.poll_once() is True and eng.serving_version() == 2
    store.rollback(bad_version=2)
    assert watcher.poll_once() is True
    assert eng.serving_version() == 1


def test_watcher_swap_fault_retries_next_poll(graph_json, tmp_path):
    store = WeightStore(str(tmp_path))
    eng = InferenceEngine(graph_json, _mlp_weights(0), input_name=IN,
                          output_name=OUT, max_batch=4)
    watcher = WeightWatcher(store, [eng], poll_interval_s=0.01)
    store.publish(_mlp_tree(graph_json, 1))
    with faults.inject("engine.swap", fail_calls=[0]):
        assert watcher.poll_once() is False
    assert watcher.stats()["swap_failures"] == 1
    assert eng.serving_version() == 0
    # the target stays unclaimed, so the next poll retries and lands it
    assert watcher.poll_once() is True
    assert eng.serving_version() == 1


def test_watcher_background_thread_swaps(graph_json, tmp_path):
    store = WeightStore(str(tmp_path))
    eng = InferenceEngine(graph_json, _mlp_weights(0), input_name=IN,
                          output_name=OUT, max_batch=4)
    watcher = WeightWatcher(store, [eng], poll_interval_s=0.02).start()
    try:
        store.publish(_mlp_tree(graph_json, 1))
        deadline = 100
        while eng.serving_version() != 1 and deadline:
            deadline -= 1
            threading.Event().wait(0.02)
        assert eng.serving_version() == 1
    finally:
        watcher.stop()
    assert watcher._thread is None


def test_watcher_rejects_non_swappable_engine(tmp_path):
    watcher = WeightWatcher(WeightStore(str(tmp_path)))
    with pytest.raises(TypeError, match="swap_params"):
        watcher.attach(object())


# -- DecodeEngine deferred swap ----------------------------------------------


VOCAB = 31


@pytest.fixture(scope="module")
def lm():
    spec = build_registry_spec("transformer_lm", vocab_size=VOCAB, hidden=16,
                               num_layers=2, num_heads=2, mlp_dim=32,
                               max_len=32, dropout=0.0)
    model = model_from_json(spec)
    params = model.init(jax.random.PRNGKey(0))
    params2 = model.init(jax.random.PRNGKey(1))
    return model, params, params2


def test_decode_swap_waits_for_token_boundary(lm):
    """A swap requested mid-request defers: admissions hold, the active
    request keeps decoding OLD weights to completion, and the swap lands at
    the drained boundary. Post-swap output is bitwise a cold start's on the
    new weights (the prefix cache cannot leak old-version K/V)."""
    model, p1, p2 = lm
    eng = DecodeEngine(model, p1, num_slots=2, page_size=8, seed=0)
    info = eng.prefill([5, 2, 8], max_new_tokens=4, temperature=0.0)
    toks = [info["token"]]
    assert eng.swap_params(p2, version=1) is False  # active slot: deferred
    st = eng.stats()
    assert st["pending_swap"] and st["serving_version"] == 0
    assert eng.can_admit(3, 2) is False  # admissions hold while pending
    while len(toks) < 4:
        toks.extend(eng.step().get(info["slot"], []))
    eng.release(info["slot"])
    assert eng.maybe_swap() is True  # drained: the swap lands
    assert eng.serving_version() == 1
    assert eng.can_admit(3, 2) is True
    # post-swap parity vs a cold engine on the new weights
    cold = DecodeEngine(model, p2, num_slots=2, page_size=8, seed=0)
    out_a = _greedy(eng, [5, 2, 8], 4)
    out_b = _greedy(cold, [5, 2, 8], 4)
    assert out_a == out_b
    assert eng.stats()["steady_traces"] == 0


def _greedy(eng, prompt, n):
    info = eng.prefill(list(prompt), max_new_tokens=n, temperature=0.0)
    toks = [info["token"]]
    while len(toks) < n:
        toks.extend(eng.step().get(info["slot"], []))
    eng.release(info["slot"])
    return toks


def test_decode_swap_immediate_when_idle(lm):
    model, p1, p2 = lm
    eng = DecodeEngine(model, p1, num_slots=2, page_size=8, seed=0)
    assert eng.swap_params(p2, version=3) is True
    assert eng.serving_version() == 3
    assert not eng.stats()["pending_swap"]
    assert _greedy(eng, [1, 2], 3) == _greedy(
        DecodeEngine(model, p2, num_slots=2, page_size=8, seed=0), [1, 2], 3)


def test_decode_watcher_nudges_deferred_swap(lm, tmp_path):
    """poll_once() nudges maybe_swap() first, so a deferred decode swap
    lands on the next poll after the engine drains — without waiting for a
    new admission to trigger it."""
    model, p1, p2 = lm
    store = WeightStore(str(tmp_path))
    eng = DecodeEngine(model, p1, num_slots=2, page_size=8, seed=0)
    watcher = WeightWatcher(store, [eng], poll_interval_s=0.01)
    info = eng.prefill([4, 4], max_new_tokens=3, temperature=0.0)
    store.publish(p2)
    # the watcher hands the version off (True); the ENGINE defers it, so
    # the serving version stays 0 until the drained boundary
    assert watcher.poll_once() is True
    assert eng.stats()["pending_swap"] and watcher.serving_version() == 0
    toks = [info["token"]]
    while len(toks) < 3:
        toks.extend(eng.step().get(info["slot"], []))
    eng.release(info["slot"])
    assert watcher.poll_once() is False  # no new version, but the nudge...
    assert eng.serving_version() == 1    # ...applies the pending swap
    assert watcher.serving_version() == 1


def test_decode_swap_mid_burst_loses_no_request(lm, tmp_path):
    """A publish mid-burst, taken up by a background watcher: every request
    delivers its whole budget, the serving version flips exactly once,
    nothing retraces, and the engine then holds the published tree bitwise."""
    model, p1, p2 = lm
    store = WeightStore(str(tmp_path))
    eng = DecodeEngine(model, p1, num_slots=2, page_size=8, seed=0)
    watcher = WeightWatcher(store, [eng], poll_interval_s=0.005).start()
    cb = ContinuousBatcher(eng, max_queue=16)
    budgets = [4, 3, 5, 3, 4, 3, 6, 3]
    try:
        futs = [cb.submit([1 + i, 2, 3], max_new_tokens=b, temperature=0.0)
                for i, b in enumerate(budgets)]
        futs[2].result(timeout=120)  # part of the burst is through
        store.publish(p2)
        assert [f.result(timeout=120)["num_tokens"] for f in futs] == budgets
        deadline = 2000  # drained: the next poll lands the swap
        while eng.serving_version() != 1 and deadline:
            deadline -= 1
            threading.Event().wait(0.005)
    finally:
        cb.close()
        watcher.stop()
    st = eng.stats()
    assert st["serving_version"] == 1 and st["swaps"] == 1
    assert not st["pending_swap"] and st["steady_traces"] == 0
    for got, want in zip(jax.tree.leaves(eng._params), jax.tree.leaves(p2)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


# -- canary health gate -------------------------------------------------------


def _feed(ctl, version, n, ok=True, latency_ms=1.0, nan=False):
    for _ in range(n):
        ctl.observe(version, ok=ok, latency_ms=latency_ms, nan=nan)


def test_canary_promotes_healthy_version():
    ctl = CanaryController(min_requests=10)
    _feed(ctl, 1, 20)           # incumbent baseline
    _feed(ctl, 2, 10)           # healthy canary
    st = ctl.stats()
    assert st["incumbent"] == 2 and st["canary"] is None
    assert st["promotions"] == 1 and st["rollbacks"] == 0


def test_canary_error_rate_rollback_repoints_store(tmp_path):
    store = WeightStore(str(tmp_path))
    store.publish(_mlp_weights(0))
    store.publish(_mlp_weights(1))
    ctl = CanaryController(min_requests=10, error_rate_margin=0.05,
                           store=store)
    _feed(ctl, 1, 20)                      # clean incumbent
    _feed(ctl, 2, 7)                       # canary: 3/10 errors
    _feed(ctl, 2, 3, ok=False)
    st = ctl.stats()
    assert st["rollbacks"] == 1 and 2 in st["quarantined"]
    assert st["canary"] is None and st["incumbent"] == 1
    # the gate repointed the store, so every watcher reverts too
    assert store.latest_version() == 1
    assert store.quarantined() == {2}


def test_canary_nan_instant_rollback():
    ctl = CanaryController(min_requests=50)
    _feed(ctl, 1, 5)
    ctl.observe(2, ok=True, latency_ms=1.0, nan=True)
    st = ctl.stats()
    assert st["rollbacks"] == 1 and 2 in st["quarantined"]
    assert st["versions"][2]["requests"] == 1  # well before min_requests


def test_canary_latency_rollback():
    ctl = CanaryController(min_requests=10, latency_factor=2.0,
                           latency_floor_ms=1.0)
    _feed(ctl, 1, 30, latency_ms=2.0)
    _feed(ctl, 2, 10, latency_ms=50.0)  # 25x the incumbent p95
    st = ctl.stats()
    assert st["rollbacks"] == 1 and 2 in st["quarantined"]


def test_canary_quarantined_version_takes_zero_traffic():
    ctl = CanaryController(min_requests=5)
    reps = [Replica("http://h:1", 0), Replica("http://h:2", 1),
            Replica("http://h:3", 2)]
    versions = {0: 1, 1: 1, 2: 2}
    vof = lambda r: versions[r.index]
    _feed(ctl, 1, 10)
    _feed(ctl, 2, 5, ok=False)  # canary fails its gate
    assert 2 in ctl.stats()["quarantined"]
    for _ in range(50):
        picked = ctl.filter_replicas(list(reps), vof)
        assert all(vof(r) == 1 for r in picked)  # v2 replicas never offered
    # observations against a quarantined version are dropped, not counted
    before = ctl.stats()["versions"][2]["requests"]
    ctl.observe(2, ok=True, latency_ms=1.0)
    assert ctl.stats()["versions"][2]["requests"] == before
    # an all-quarantined candidate list yields [] (503 beats bad weights)
    assert ctl.filter_replicas([reps[2]], vof) == []


def test_canary_fraction_splits_preference():
    ctl = CanaryController(min_requests=10 ** 6, canary_fraction=0.5, seed=7)
    reps = [Replica("http://h:1", 0), Replica("http://h:2", 1)]
    versions = {0: 1, 1: 2}
    vof = lambda r: versions[r.index]
    first = {1: 0, 2: 0}
    for _ in range(200):
        first[vof(ctl.filter_replicas(list(reps), vof)[0])] += 1
    # both orders occur; the canary leads roughly canary_fraction of picks
    assert 40 <= first[2] <= 160


def test_canary_gauges_published():
    m = Metrics()
    ctl = CanaryController(min_requests=10, metrics=m)
    _feed(ctl, 1, 5)
    _feed(ctl, 2, 3)
    ctl.publish_gauges()
    g = m.summary()["gauges"]
    assert g["serving/version1/requests"] == 5.0
    assert g["serving/version2/requests"] == 3.0
    assert g["serving/canary/incumbent"] == 1.0
    assert g["serving/canary/version"] == 2.0


# -- trainer / elastic publication -------------------------------------------


def _clf_graph():
    x = nn.placeholder([None, 10], name="x")
    y = nn.placeholder([None, 2], name="y")
    h = nn.dense(x, 8, activation="relu")
    out = nn.dense(h, 2, name="out")
    nn.softmax_cross_entropy(y, out)


@pytest.fixture(scope="module")
def clf_data():
    rs = np.random.RandomState(0)
    X = rs.randn(64, 10).astype(np.float32)
    lbl = (X @ rs.randn(10) > 0).astype(int)
    return X, np.eye(2)[lbl].astype(np.float32)


def test_trainer_publishes_on_cadence(tmp_path, clf_data):
    """publish_every=2 over 4 epochs publishes versions [1, 2] and the
    final published tree is bitwise the fit's result params — what a
    WeightWatcher would hand every serving replica."""
    X, Y = clf_data
    store = WeightStore(str(tmp_path))
    tr = Trainer(build_graph(_clf_graph), "x:0", "y:0", iters=4,
                 mini_batch_size=32, publish_to=store, publish_every=2)
    res = tr.fit(X, Y)
    assert store.all_versions() == [1, 2]
    v, got = store.load(like=res.params)
    assert v == 2 and _bitwise(got, res.params)


def test_trainer_publishes_at_fit_end(tmp_path, clf_data):
    """publish_to without publish_every: one publish of the final weights
    (the fused multi-epoch path included)."""
    X, Y = clf_data
    d = str(tmp_path / "end")
    tr = Trainer(build_graph(_clf_graph), "x:0", "y:0", iters=3,
                 mini_batch_size=32, publish_to=d)
    res = tr.fit(X, Y)
    store = WeightStore(d)
    assert store.all_versions() == [1]
    v, got = store.load(like=res.params)
    assert v == 1 and _bitwise(got, res.params)


def test_elastic_store_publishes_on_accepted_pushes(tmp_path, clf_data):
    """strategy='elastic_dp' threads publish_to/publish_every into the
    ElasticParamStore: every Nth ACCEPTED push lands a verifiable version."""
    X, Y = clf_data
    d = str(tmp_path / "elastic")
    tr = Trainer(build_graph(_clf_graph), "x:0", "y:0", iters=2,
                 mini_batch_size=32, strategy="elastic_dp",
                 elastic={"replicas": 2}, publish_to=d, publish_every=2)
    res = tr.fit(X, Y)
    store = WeightStore(d)
    assert store.all_versions(), "no versions published from elastic fit"
    v, got = store.load(like=res.params)
    assert v == store.latest_version()
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(got))


def test_publish_failure_never_fails_training(tmp_path, clf_data,
                                              monkeypatch):
    X, Y = clf_data
    store = WeightStore(str(tmp_path))

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(store, "publish", boom)
    tr = Trainer(build_graph(_clf_graph), "x:0", "y:0", iters=2,
                 mini_batch_size=32, publish_to=store, publish_every=1)
    res = tr.fit(X, Y)  # must complete despite every publish failing
    assert res.stop_reason == "completed"
    assert np.isfinite(res.losses).all()


# -- static gates -------------------------------------------------------------


@pytest.mark.parametrize("fname", ["weightstore.py", "engine.py",
                                   "router.py"])
def test_lock_lint_clean(fname):
    """GC-L301/302/303: every shared-state write in the weight-publication
    code happens under the owning lock."""
    path = os.path.join(REPO, "sparkflow_tpu", "serving", fname)
    findings = locks.lint_file(path)
    bad = [f for f in findings
           if f.rule in ("GC-L301", "GC-L302", "GC-L303")]
    assert not bad, "\n".join(f"{f.rule}: {f.message}" for f in bad)


def test_lock_graph_sees_weightstore_and_stays_acyclic():
    """The lock-order graph knows the new locks and the whole-package graph
    stays cycle-free — the watcher takes engine locks only via calls made
    OUTSIDE its own lock, so no watcher→engine edge can close a cycle."""
    g = lockgraph.build_graph([os.path.join(REPO, "sparkflow_tpu")])
    known = set(g.node_ctor)
    assert "sparkflow_tpu.serving.weightstore.WeightStore._lock" in known
    assert "sparkflow_tpu.serving.weightstore.WeightWatcher._lock" in known
    assert "sparkflow_tpu.serving.router.CanaryController._lock" in known
    sccs = [c for c in lockgraph._sccs(g.edges) if len(c) > 1]
    assert sccs == [], f"lock-order cycle: {sccs}"
    fs = lockgraph.lint_paths([os.path.join(REPO, "sparkflow_tpu")])
    assert fs == [], "\n" + "\n".join(f.render() for f in fs)


def test_swap_path_race_clean_under_lockset_detector(graph_json, tmp_path):
    """GC-R402: hammer predict + swap_params from concurrent threads with
    the engine's swap-guarded fields instrumented — the double-buffered
    swap discipline holds under the dynamic lockset detector."""
    store = WeightStore(str(tmp_path))
    eng = InferenceEngine(graph_json, _mlp_weights(0), input_name=IN,
                          output_name=OUT, max_batch=4)
    watcher = WeightWatcher(store, [eng], poll_interval_s=0.001)
    x = np.zeros((2, 4), np.float32)
    with racecheck.RaceTracker() as tracker:
        racecheck.instrument_object(
            eng, fields=("_params", "_serving_version", "_swaps"))
        stop = threading.Event()

        def serve():
            while not stop.is_set():
                eng.predict(x)

        def publish_and_poll():
            for s in range(1, 6):
                store.publish(_mlp_tree(graph_json, s))
                watcher.poll_once()

        t = threading.Thread(target=serve)
        t.start()
        try:
            publish_and_poll()
        finally:
            stop.set()
            t.join()
    tracker.assert_clean()
    assert eng.serving_version() == 5
