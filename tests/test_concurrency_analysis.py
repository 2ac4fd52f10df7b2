"""graftcheck v2 concurrency analyzers: planted defects fire, clean code
passes, the repo itself gates clean.

Covers the three analyzers of the concurrency-soundness layer:

- GC-L304/L305 (:mod:`sparkflow_tpu.analysis.lockgraph`): a two-lock cycle
  planted ACROSS two synthetic modules, blocking ops under a held lock,
  and the inline-suppression contract (suppressed site silent, an
  unsuppressed duplicate in the same file still fires);
- GC-R402 (:mod:`sparkflow_tpu.analysis.racecheck`): a racy unguarded
  counter hit from two real threads reports exactly once with both access
  stacks; the same counter under a lock — or read-only after publication —
  stays silent; instrumentation is a no-op without an installed tracker;
- GC-J107 (:mod:`sparkflow_tpu.analysis.jaxpr_lint`): a ``psum`` under
  ``lax.cond`` / inside ``lax.while_loop`` is flagged, the hoisted version
  and static ``lax.scan`` pass.

Plus the whole-repo gates: the lock graph over ``sparkflow_tpu`` +
``examples`` is cycle-free with zero unsuppressed findings, and the
elastic threaded driver runs clean under the lockset detector.
"""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sparkflow_tpu.analysis import jaxpr_lint, lockgraph, racecheck

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# GC-L304: lock-order cycles
# ---------------------------------------------------------------------------

_MOD_A = '''
import threading


class Alpha:
    def __init__(self, peer: "Beta"):
        self._lock = threading.Lock()
        self.peer = peer

    def hit(self):
        with self._lock:
            self.peer.poke()   # Alpha._lock -> Beta._lock

    def poke(self):
        with self._lock:
            return 1
'''

_MOD_B_CYCLIC = '''
import threading


class Beta:
    def __init__(self, back: "Alpha" = None):
        self._lock = threading.Lock()
        self.back = back

    def hit(self):
        with self._lock:
            self.back.poke()   # Beta._lock -> Alpha._lock: the inversion

    def poke(self):
        with self._lock:
            return 2
'''

_MOD_B_CLEAN = '''
import threading


class Beta:
    def __init__(self, back: "Alpha" = None):
        self._lock = threading.Lock()
        self.back = back

    def hit(self):
        self.back.poke()       # outside the lock: consistent order
        with self._lock:
            return 2

    def poke(self):
        with self._lock:
            return 2
'''


def _write_pkg(tmp_path, mod_b_src):
    (tmp_path / "mod_a.py").write_text(_MOD_A)
    (tmp_path / "mod_b.py").write_text(mod_b_src)
    return str(tmp_path)


def test_l304_cross_module_cycle_detected(tmp_path):
    fs = lockgraph.lint_paths([_write_pkg(tmp_path, _MOD_B_CYCLIC)])
    cycles = [f for f in fs if f.rule == "GC-L304"]
    assert cycles, "the planted Alpha/Beta inversion was not reported"
    cyc = cycles[0].detail["cycle"]
    assert any("Alpha._lock" in n for n in cyc)
    assert any("Beta._lock" in n for n in cyc)
    # the report names both legs with file:line sites
    assert "mod_a.py" in cycles[0].message
    assert "mod_b.py" in cycles[0].message


def test_l304_consistent_order_clean(tmp_path):
    fs = lockgraph.lint_paths([_write_pkg(tmp_path, _MOD_B_CLEAN)])
    assert [f for f in fs if f.rule == "GC-L304"] == [], \
        "\n".join(f.render() for f in fs)


def test_l304_self_reacquire_through_call_chain(tmp_path):
    # non-reentrant lock re-acquired via an intra-class call: self-deadlock
    (tmp_path / "mod_c.py").write_text('''
import threading


class Gamma:
    def __init__(self):
        self._lock = threading.Lock()

    def outer(self):
        with self._lock:
            self.inner()

    def inner(self):
        with self._lock:
            return 3
''')
    fs = lockgraph.lint_paths([str(tmp_path)])
    assert any(f.rule == "GC-L304" and "re-acquired" in f.message
               for f in fs), "\n".join(f.render() for f in fs)


def test_l304_rlock_reentry_exempt(tmp_path):
    (tmp_path / "mod_d.py").write_text('''
import threading


class Delta:
    def __init__(self):
        self._lock = threading.RLock()

    def outer(self):
        with self._lock:
            self.inner()

    def inner(self):
        with self._lock:
            return 4
''')
    fs = lockgraph.lint_paths([str(tmp_path)])
    assert fs == [], "\n".join(f.render() for f in fs)


# ---------------------------------------------------------------------------
# GC-L305: blocking under a held lock (+ suppression contract)
# ---------------------------------------------------------------------------

_SLEEPER = '''
import threading
import time


class Sleeper:
    def __init__(self):
        self._lock = threading.Lock()

    def nap(self):
        with self._lock:
            time.sleep(0.1)
'''


def test_l305_sleep_under_lock_detected(tmp_path):
    (tmp_path / "mod_s.py").write_text(_SLEEPER)
    fs = lockgraph.lint_paths([str(tmp_path)])
    hits = [f for f in fs if f.rule == "GC-L305"]
    assert len(hits) == 1
    assert "sleep" in hits[0].message
    assert "Sleeper._lock" in hits[0].message


def test_l305_sleep_outside_lock_clean(tmp_path):
    (tmp_path / "mod_s.py").write_text(_SLEEPER.replace(
        "        with self._lock:\n            time.sleep(0.1)",
        "        with self._lock:\n            pass\n        time.sleep(0.1)"))
    fs = lockgraph.lint_paths([str(tmp_path)])
    assert fs == [], "\n".join(f.render() for f in fs)


def test_l305_blocking_through_call_chain(tmp_path):
    # the blocking op hides one call away; the lint must follow the chain
    (tmp_path / "mod_t.py").write_text('''
import threading
import time


class Chained:
    def __init__(self):
        self._lock = threading.Lock()

    def entry(self):
        with self._lock:
            self._helper()

    def _helper(self):
        time.sleep(0.5)
''')
    fs = lockgraph.lint_paths([str(tmp_path)])
    hits = [f for f in fs if f.rule == "GC-L305"]
    assert len(hits) == 1
    assert "_helper" in hits[0].message


def test_l305_suppressed_site_silent_unsuppressed_duplicate_fires(tmp_path):
    # the satellite contract: an inline disable quiets EXACTLY its line;
    # an identical unsuppressed defect in the same file still fires
    (tmp_path / "mod_u.py").write_text('''
import threading
import time


class Two:
    def __init__(self):
        self._lock = threading.Lock()

    def intentional(self):
        with self._lock:
            time.sleep(0.1)  # graftcheck: disable=GC-L305

    def accidental(self):
        with self._lock:
            time.sleep(0.1)
''')
    fs = lockgraph.lint_paths([str(tmp_path)])
    hits = [f for f in fs if f.rule == "GC-L305"]
    assert len(hits) == 1, "\n".join(f.render() for f in fs)
    assert "accidental" in hits[0].message
    assert hits[0].line == 16  # the unsuppressed duplicate's sleep


def test_condition_wait_exempt_event_wait_flagged(tmp_path):
    # Condition.wait releases the lock (the point of a condition); a bare
    # Event.wait under the lock stalls every contender
    (tmp_path / "mod_w.py").write_text('''
import threading


class Waits:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._evt = threading.Event()

    def good(self):
        with self._cond:
            self._cond.wait()

    def bad(self):
        with self._lock:
            self._evt.wait()
''')
    fs = lockgraph.lint_paths([str(tmp_path)])
    hits = [f for f in fs if f.rule == "GC-L305"]
    assert len(hits) == 1
    assert "Event" in hits[0].message


# ---------------------------------------------------------------------------
# GC-R402: dynamic lockset race detection
# ---------------------------------------------------------------------------


class _Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0


def _hammer(fn, nthreads=2):
    threads = [threading.Thread(target=fn) for _ in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def test_r402_unguarded_counter_reported_with_stacks():
    with racecheck.RaceTracker() as tracker:
        c = _Counter()
        racecheck.instrument_object(c, fields=("n",))

        def bump():
            for _ in range(500):
                c.n += 1

        _hammer(bump)
    fs = tracker.findings()
    assert len(fs) == 1, [f.render() for f in fs]  # reported once, not 500x
    f = fs[0]
    assert f.rule == "GC-R402"
    assert "_Counter.n" in f.message
    # both access stacks present and pointing at the racy line
    assert "bump" in str(f.detail["first_stack"]) or \
        "bump" in str(f.detail["second_stack"])
    assert "bump" in str(f.detail["race_stack"])
    assert len(f.detail["threads"]) >= 2
    with pytest.raises(AssertionError):
        tracker.assert_clean()


def test_r402_sequential_nonoverlapping_threads_still_report():
    # the OS reuses thread idents: a worker that fully finishes before its
    # sibling starts can hand the sibling the SAME get_ident() value, which
    # used to alias both into one "thread" and silently miss the race (the
    # exact interleaving a loaded 1-core run produces). The tracker now
    # assigns its own per-thread serials, so two non-overlapping threads
    # touching an unguarded field must still report.
    with racecheck.RaceTracker() as tracker:
        c = _Counter()
        racecheck.instrument_object(c, fields=("n",))

        def bump():
            for _ in range(50):
                c.n += 1

        for _ in range(2):          # start/join one at a time: zero overlap
            t = threading.Thread(target=bump)
            t.start()
            t.join()
    fs = tracker.findings()
    assert len(fs) == 1 and fs[0].rule == "GC-R402", \
        [f.render() for f in fs]
    assert len(fs[0].detail["threads"]) >= 2


def test_r402_guarded_counter_clean():
    with racecheck.RaceTracker() as tracker:
        c = _Counter()
        racecheck.instrument_object(c, fields=("n",))

        def bump():
            for _ in range(500):
                with c._lock:
                    c.n += 1

        _hammer(bump)
    tracker.assert_clean()


def test_r402_read_only_after_publish_clean():
    # immutable-after-init fields read lock-free are NOT races (the Eraser
    # shared state): this is why the detector doesn't drown in config reads
    with racecheck.RaceTracker() as tracker:
        c = _Counter()
        racecheck.instrument_object(c, fields=("n",))
        c.n = 42
        seen = []
        _hammer(lambda: seen.append(c.n), nthreads=4)
    tracker.assert_clean()
    assert seen == [42] * 4


def test_r402_condition_wait_releases_lock_in_lockset():
    # cond.wait() must drop the lock from the waiter's lockset while it
    # sleeps and re-add it on wake — no false positive, no false negative
    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._cond = threading.Condition(self._lock)
            self.v = 0

    with racecheck.RaceTracker() as tracker:
        b = Box()
        racecheck.instrument_object(b, fields=("v",))

        def producer():
            for _ in range(50):
                with b._cond:
                    b.v += 1
                    b._cond.notify_all()

        def consumer():
            with b._cond:
                while b.v < 50:
                    b._cond.wait(timeout=2.0)

        threads = [threading.Thread(target=producer),
                   threading.Thread(target=consumer)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    tracker.assert_clean()


def test_racecheck_noop_without_tracker():
    # zero-overhead contract: with no tracker installed the object is
    # untouched — same class, raw lock, no tracking properties
    assert racecheck.active() is None
    c = _Counter()
    cls_before = type(c)
    lock_before = c._lock
    racecheck.instrument_object(c, fields=("n",))
    assert type(c) is cls_before
    assert c._lock is lock_before
    assert racecheck.tracked(c, "n") is c
    assert type(c) is cls_before


def test_racecheck_env_flag():
    old = os.environ.pop("SPARKFLOW_TPU_RACECHECK", None)
    try:
        assert not racecheck.enabled()
        os.environ["SPARKFLOW_TPU_RACECHECK"] = "1"
        assert racecheck.enabled()
        os.environ["SPARKFLOW_TPU_RACECHECK"] = "0"
        assert not racecheck.enabled()
    finally:
        if old is None:
            os.environ.pop("SPARKFLOW_TPU_RACECHECK", None)
        else:
            os.environ["SPARKFLOW_TPU_RACECHECK"] = old


def test_elastic_threaded_driver_clean_under_tracker():
    # the wired chaos harness: ElasticDPEngine.run_threads instruments its
    # store when a tracker is active; the real protocol must be race-free
    from sparkflow_tpu.parallel.elastic import ElasticDPEngine

    def loss_fn(params, x, y, mask, rng):
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2)

    rs = np.random.RandomState(0)
    X = rs.rand(64, 3).astype(np.float32)
    Y = (X @ np.array([[1.0], [-1.0], [0.5]], np.float32)).astype(np.float32)
    eng = ElasticDPEngine(loss_fn, optax.sgd(0.05),
                          {"w": jnp.zeros((3, 1))})
    with racecheck.RaceTracker() as tracker:
        res = eng.run_threads([(X[0::2], Y[0::2]), (X[1::2], Y[1::2])],
                              epochs=3, batch_size=16, seed=0)
    assert res.examples > 0
    tracker.assert_clean()
    # the instrumentation actually engaged: store fields were tracked
    assert any("_version" in fs.label
               for fs in tracker._fields.values())


# ---------------------------------------------------------------------------
# GC-J107: collectives under data-dependent control flow
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def test_j107_psum_under_cond_detected(one_mesh):
    def bad(v):
        return lax.cond(v.sum() > 0,
                        lambda u: lax.psum(u, "dp"),
                        lambda u: u * 2.0, v)

    fs = jaxpr_lint.lint_collective_divergence(
        bad, (jnp.ones((4, 2)),), mesh=one_mesh, in_specs=(P("dp"),),
        out_specs=P("dp"))
    assert len(fs) == 1 and fs[0].rule == "GC-J107"
    assert fs[0].detail["control"] == "cond"
    assert "psum" in str(fs[0].detail["collectives"])


def test_j107_psum_hoisted_clean(one_mesh):
    def good(v):
        s = lax.psum(v, "dp")
        return lax.cond(v.sum() > 0, lambda u: u, lambda u: u * 2.0, s)

    fs = jaxpr_lint.lint_collective_divergence(
        good, (jnp.ones((4, 2)),), mesh=one_mesh, in_specs=(P("dp"),),
        out_specs=P("dp"))
    assert fs == [], [f.render() for f in fs]


def test_j107_psum_in_while_body_detected(one_mesh):
    def bad(v):
        def body(c):
            i, u = c
            return i + 1, lax.psum(u, "dp")
        return lax.while_loop(lambda c: c[0] < 3, body, (0, v))[1]

    fs = jaxpr_lint.lint_collective_divergence(
        bad, (jnp.ones((4, 2)),), mesh=one_mesh, in_specs=(P("dp"),),
        out_specs=P("dp"))
    assert len(fs) == 1 and fs[0].detail["control"] == "while"


def test_j107_scan_is_static_and_clean(one_mesh):
    # scan's trip count is static — every device agrees — so a collective
    # in a scan body is NOT divergence
    def good(v):
        def body(c, _):
            return lax.psum(c, "dp"), None
        return lax.scan(body, v, None, length=3)[0]

    fs = jaxpr_lint.lint_collective_divergence(
        good, (jnp.ones((4, 2)),), mesh=one_mesh, in_specs=(P("dp"),),
        out_specs=P("dp"))
    assert fs == [], [f.render() for f in fs]


def test_j107_ignore_and_lint_fn_integration(one_mesh):

    def bad(v):
        return lax.cond(v.sum() > 0,
                        lambda u: lax.psum(u, "dp"),
                        lambda u: u * 2.0, v)

    fs = jaxpr_lint.lint_collective_divergence(
        bad, (jnp.ones((4, 2)),), mesh=one_mesh, in_specs=(P("dp"),),
        out_specs=P("dp"), ignore=("GC-J107",))
    assert fs == []
    # the generic lint_fn entry point sees it too (shard_map'd by hand)
    wrapped = jax.shard_map(bad, mesh=one_mesh, in_specs=(P("dp"),),
                        out_specs=P("dp"), check_vma=False)
    fs2 = jaxpr_lint.lint_fn(wrapped, (jnp.ones((4, 2)),),
                             ignore=("GC-J103", "GC-J104"))
    assert any(f.rule == "GC-J107" for f in fs2)


# ---------------------------------------------------------------------------
# whole-repo gates
# ---------------------------------------------------------------------------


def test_repo_lock_graph_clean():
    paths = [os.path.join(REPO, "sparkflow_tpu"),
             os.path.join(REPO, "examples")]
    fs = lockgraph.lint_paths(paths)
    assert fs == [], "\n" + "\n".join(f.render() for f in fs)


def test_repo_lock_graph_is_acyclic_with_real_edges():
    # the serving plane's documented hierarchy: engines/batchers take their
    # own lock, then (transitively) the KV pool's, then Metrics' — never
    # the other way. The graph must SEE those edges (the analysis has
    # teeth) and contain no multi-node SCC.
    g = lockgraph.build_graph([os.path.join(REPO, "sparkflow_tpu")])
    flat = {(src, dst) for src, tgts in g.edges.items() for dst in tgts}
    assert ("sparkflow_tpu.serving.kvcache.PagedKVCache._lock",
            "sparkflow_tpu.utils.metrics.Metrics._lock") in flat
    assert ("sparkflow_tpu.serving.decode.DecodeEngine._lock",
            "sparkflow_tpu.serving.kvcache.PagedKVCache._lock") in flat
    sccs = [c for c in lockgraph._sccs(g.edges) if len(c) > 1]
    assert sccs == [], f"lock-order cycle in the repo: {sccs}"


def test_native_build_allowlist_is_line_anchored():
    # the one intentional L305 site (subprocess.run under the native build
    # lock) is suppressed by an inline comment, not by weakening the rule:
    # the raw findings must still contain it
    path = os.path.join(REPO, "sparkflow_tpu", "native", "build.py")
    g = lockgraph.build_graph([os.path.join(REPO, "sparkflow_tpu")])
    raw = lockgraph._graph_findings(g)
    assert any(f.rule == "GC-L305" and f.path == path for f in raw), \
        "expected the intentional native-build site in the raw findings"
    assert lockgraph._filter_by_file(raw) == [
        f for f in lockgraph._filter_by_file(raw) if f.path != path]
