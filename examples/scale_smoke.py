"""Elastic autoscaling smoke: a self-healing fleet under a load step.

Run via ``make scale-smoke`` (or directly). The script

1. boots ONE replica process (re-invoking itself with ``--replica PORT``)
   behind a :class:`RouterServer`, with an :class:`Autoscaler` +
   :class:`ReplicaManager` supervising the fleet (``min=1, max=3``,
   tight hysteresis bands so the whole loop fits in seconds). Replicas
   share an :class:`ExecutableStore` directory, so every replica after
   the first boots its predict ladder from serialized executables —
   zero compiles on the scale-up path;
2. steps the load up (concurrent workers against a deliberately slow
   engine): queue-wait p95 crosses the high band and the autoscaler
   spawns replicas;
3. SIGKILLs one replica mid-burst: the router reroutes its in-flight
   work, the autoscaler reaps the exit code and spawns a replacement
   within one tick;
4. steps the load down to a trickle: p95 falls through the low band and
   the autoscaler SIGTERM-drains the fleet back toward ``min``;
5. asserts zero client-visible failures across the whole run (the
   client retries nothing — every recovery is the router's and the
   autoscaler's doing), that the fleet actually grew, replaced the
   kill, and shrank, and that at least one spawned replica cold-started
   from the executable store.

Everything runs on CPU (``JAX_PLATFORMS=cpu``) in under a minute.
"""

import argparse
import atexit
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import sparkflow_tpu.nn as nn
from sparkflow_tpu.graph_utils import build_graph
from sparkflow_tpu.serving import (Autoscaler, InferenceEngine,
                                   InferenceServer, ReplicaManager,
                                   RouterServer, ServingClient, policies)

BURST_WORKERS = 12
BURST_S = 8.0
TRICKLE_S = 8.0
SERVICE_DELAY_S = 0.03  # per-batch model "work": makes saturation honest


def mlp_graph():
    x = nn.placeholder([None, 4], name="x")
    h = nn.dense(x, 3, activation="relu")
    out = nn.dense(h, 2, name="out")
    nn.mean_squared_error(x, out)


class SlowEngine(InferenceEngine):
    """The MLP with a fixed per-batch service time, so one replica
    saturates under the burst and the queue-wait signal means something."""

    def predict(self, x):
        time.sleep(SERVICE_DELAY_S)
        return super().predict(x)


def make_engine() -> InferenceEngine:
    rs = np.random.RandomState(0)  # every replica serves identical weights
    weights = [rs.randn(4, 3).astype(np.float32),
               rs.randn(3).astype(np.float32),
               rs.randn(3, 2).astype(np.float32),
               rs.randn(2).astype(np.float32)]
    return SlowEngine(build_graph(mlp_graph), weights,
                      input_name="x:0", output_name="out/BiasAdd:0",
                      max_batch=4,
                      executable_dir=os.environ.get("SCALE_SMOKE_EXEDIR"))


def run_replica(port: int) -> None:
    from sparkflow_tpu.resilience.lifecycle import ServerState
    engine = make_engine()
    cs = engine.stats().get("cold_start") or {}
    server = InferenceServer(engine, port=port, max_delay_ms=5.0)
    server.start()
    server.install_signal_handlers()
    print(f"replica up on {server.url} "
          f"serialized_loads={cs.get('serialized_loads', 0)}", flush=True)
    while server.lifecycle.state in (ServerState.STARTING,
                                     ServerState.SERVING):
        time.sleep(0.2)
    server.stop()


def spawn_replica(port: int) -> subprocess.Popen:
    return subprocess.Popen([sys.executable, __file__, "--replica",
                             str(port)])


def wait_healthy(url: str, timeout_s: float = 90.0) -> None:
    client = ServingClient(url, retries=0)
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        try:
            if client.healthz(timeout_s=1.0)["status"] == "ok":
                client.close()
                return
        except Exception:
            pass
        time.sleep(0.2)
    raise TimeoutError(f"replica at {url} never became healthy")


def main() -> None:
    exedir = tempfile.mkdtemp(prefix="scale_smoke_exe_")
    # replicas (including replacements spawned during teardown) read the
    # store until the very end, so the dir comes down at process exit
    atexit.register(shutil.rmtree, exedir, ignore_errors=True)
    os.environ["SCALE_SMOKE_EXEDIR"] = exedir

    # founding replica, by hand; the manager adopts its process
    from sparkflow_tpu.serving.autoscaler import free_port
    port0 = free_port()
    proc0 = spawn_replica(port0)
    url0 = f"http://127.0.0.1:{port0}"
    wait_healthy(url0)

    router = RouterServer([url0], probe_interval_s=0.2, dispatch_retries=4,
                          max_inflight=2 * BURST_WORKERS)
    # SPARKFLOW_TPU_RESTRACK=1: every router/replica<i>/* gauge family a
    # spawned/drained/replaced replica publishes must leave the registry
    # with it (deregister or stop) — churn is this smoke's whole point, so
    # it doubles as the gauge-leak oracle
    from sparkflow_tpu.analysis import restrack
    retracker = restrack.ResourceTracker().install() \
        if restrack.enabled() else None
    if retracker is not None:
        restrack.instrument_metrics(router.metrics,
                                    prefixes=("router/replica",))
    router.start()
    manager = ReplicaManager(spawn_replica,
                             membership=router.membership,
                             health_timeout_s=90.0, drain_timeout_s=10.0)
    manager.adopt(router.membership.replicas[0], proc0)
    scaler = Autoscaler(
        router.membership, manager,
        targets=policies.ScaleTargets(
            min_replicas=1, max_replicas=3,
            queue_wait_high_ms=120.0, queue_wait_low_ms=60.0,
            up_cooldown_s=1.5, down_cooldown_s=3.0, max_step_up=1),
        interval_s=0.5, signal_window=64).start()

    errors = []
    stop_burst = threading.Event()

    def worker(wid: int) -> None:
        client = ServingClient(router.url, retries=0, timeout=30.0)
        x = [[0.1 * wid, 0.2, 0.3, 0.4]]
        while not stop_burst.is_set():
            try:
                client.predict(x)
            except Exception as exc:  # noqa: BLE001 - any failure counts
                errors.append(f"worker{wid}: {exc}")
        client.close()

    procs_killed = 0
    clean = False
    try:
        # -- step up: saturate the singleton fleet ---------------------------
        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(BURST_WORKERS)]
        for t in threads:
            t.start()
        deadline = time.time() + 4 * BURST_S
        while time.time() < deadline and scaler.spawns < 1:
            time.sleep(0.25)
        assert scaler.spawns >= 1, "burst never triggered a scale-up"

        # -- chaos: SIGKILL a replica mid-burst ------------------------------
        victim = manager.managed()[-1]
        vproc = manager._managed[victim.index].proc
        vproc.send_signal(signal.SIGKILL)
        vproc.wait(timeout=10.0)
        procs_killed += 1
        deadline = time.time() + 4 * BURST_S
        while time.time() < deadline and scaler.replacements < 1:
            time.sleep(0.25)
        assert scaler.replacements >= 1, "kill was never replaced"
        time.sleep(BURST_S / 2)  # let the replacement take traffic

        # -- step down: trickle load, fleet shrinks back ---------------------
        stop_burst.set()
        for t in threads:
            t.join(timeout=30.0)
        client = ServingClient(router.url, retries=0, timeout=30.0)
        deadline = time.time() + 6 * TRICKLE_S
        while time.time() < deadline and scaler.drains < 1:
            try:
                client.predict([[0.1, 0.2, 0.3, 0.4]])
            except Exception as exc:  # noqa: BLE001
                errors.append(f"trickle: {exc}")
            time.sleep(0.1)
        client.close()
        assert scaler.drains >= 1, "idle fleet never scaled down"

        assert errors == [], (
            f"{len(errors)} client-visible failures: {errors[:5]}")
        healthy = router.membership.healthy_count()
        assert healthy >= 1, f"fleet ended unhealthy ({healthy})"
        g = router.metrics.gauges()
        print(f"scale smoke OK: spawns={scaler.spawns} "
              f"replacements={scaler.replacements} drains={scaler.drains} "
              f"killed={procs_killed} fleet={healthy} "
              f"client_failures={len(errors)} "
              f"gauges={ {k: v for k, v in g.items() if k.startswith('autoscaler/')} }",
              flush=True)
        clean = True
    finally:
        stop_burst.set()
        scaler.stop()
        manager.stop_all(kill=True)
        router.stop()
        if retracker is not None:
            retracker.uninstall()
            if clean:  # don't shadow a real failure with its leaks
                retracker.assert_balanced()
                print(f"restrack: zero unbalanced resources "
                      f"({retracker.acquired} gauge families acquired, "
                      f"{retracker.released} released)", flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--replica", type=int, default=None)
    args = ap.parse_args()
    if args.replica is not None:
        run_replica(args.replica)
    else:
        main()
