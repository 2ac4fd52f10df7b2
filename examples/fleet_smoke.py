"""Fleet chaos smoke: 3 real replica processes behind a RouterServer.

Run via ``make fleet-smoke`` (or directly). The script

1. spawns three replica *processes* (re-invoking itself with
   ``--replica PORT``), each an :class:`InferenceServer` over a tiny AOT
   MLP engine with SIGTERM drain handlers installed;
2. starts a :class:`RouterServer` in front of them (health probes,
   circuit breakers, least-loaded dispatch, retry/reroute);
3. drives sustained concurrent load through a plain :class:`ServingClient`
   pointed at the router with client-side retries DISABLED — every
   recovery below is the router's doing;
4. mid-burst, SIGKILLs one replica, then restarts it on the same port;
5. asserts zero client-visible failures, that every response echoed its
   originating ``X-Request-Id``, and that the restarted replica rejoined
   the rotation (healthy_replicas back to 3).

Everything runs on CPU (`JAX_PLATFORMS=cpu`) in a few seconds.
"""

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import sparkflow_tpu.nn as nn
from sparkflow_tpu.graph_utils import build_graph
from sparkflow_tpu.serving import (InferenceEngine, InferenceServer,
                                   RouterServer, ServingClient)

N_REPLICAS = 3
WORKERS = 6
REQUESTS_PER_WORKER = 15


def mlp_graph():
    x = nn.placeholder([None, 4], name="x")
    h = nn.dense(x, 3, activation="relu")
    out = nn.dense(h, 2, name="out")
    nn.mean_squared_error(x, out)


def make_engine() -> InferenceEngine:
    rs = np.random.RandomState(0)  # every replica serves identical weights
    weights = [rs.randn(4, 3).astype(np.float32),
               rs.randn(3).astype(np.float32),
               rs.randn(3, 2).astype(np.float32),
               rs.randn(2).astype(np.float32)]
    return InferenceEngine(build_graph(mlp_graph), weights,
                           input_name="x:0", output_name="out/BiasAdd:0",
                           max_batch=16)


def run_replica(port: int) -> None:
    from sparkflow_tpu.resilience.lifecycle import ServerState
    server = InferenceServer(make_engine(), port=port, max_delay_ms=1.0)
    server.start()
    server.install_signal_handlers()
    print(f"replica up on {server.url}", flush=True)
    # serve until SIGTERM flips the lifecycle to DRAINING, then finish
    # in-flight work and exit (drain leaves the socket up; stop tears down)
    while server.lifecycle.state in (ServerState.STARTING,
                                     ServerState.SERVING):
        time.sleep(0.2)
    server.stop()


def free_ports(n: int):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def spawn_replica(port: int) -> subprocess.Popen:
    return subprocess.Popen([sys.executable, __file__, "--replica",
                             str(port)])


def wait_healthy(url: str, timeout_s: float = 60.0) -> None:
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
    from sparkflow_tpu.analysis import racecheck, restrack

    ports = free_ports(N_REPLICAS)
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    procs = {p: spawn_replica(p) for p in ports}
    errors, echoes = [], []
    router = None
    # SPARKFLOW_TPU_RACECHECK=1 runs the whole chaos burst under the Eraser
    # lockset detector (zero overhead otherwise); any empty-lockset field in
    # the router's shared state fails the smoke with both access stacks
    tracker = racecheck.RaceTracker().install() if racecheck.enabled() \
        else None
    # SPARKFLOW_TPU_RESTRACK=1 additionally audits resource balance: every
    # pooled-connection checkout must be returned and every
    # router/replica<i>/* gauge family must leave the registry with its
    # replica (deregister or stop), or the smoke fails with the stacks
    retracker = restrack.ResourceTracker().install() \
        if restrack.enabled() else None
    clean = False
    try:
        for u in urls:
            wait_healthy(u)
        router = RouterServer(urls, probe_interval_s=0.1, recovery_s=0.3,
                              dispatch_retries=5)
        if tracker is not None:  # before start(): threads must see wrappers
            # wrap Membership._lock FIRST — it is the lock guarding every
            # per-replica field below; without the wrapper the tracker
            # can't see it held and reports false empty locksets
            racecheck.instrument_object(router.membership, name="Membership")
            for rep in router.membership._replicas:
                racecheck.instrument_object(
                    rep, fields=("healthy", "inflight", "queue_depth",
                                 "successes", "failures"),
                    name=f"Replica{rep.index}")
                racecheck.instrument_object(
                    rep.breaker, fields=("_state", "_consecutive_failures"),
                    name=f"Replica{rep.index}.breaker")
            if router.cache is not None:
                racecheck.instrument_object(
                    router.cache, fields=("hits", "misses"),
                    name="ResultCache")
        if retracker is not None:  # before start(), like racecheck
            restrack.instrument_metrics(router.metrics,
                                        prefixes=("router/replica",))
            for rep in router.membership._replicas:
                restrack.instrument_pool(rep.pool)
        router.start()
        print(f"router up on {router.url} fronting {N_REPLICAS} replicas",
              flush=True)

        def worker(k: int) -> None:
            client = ServingClient(router.url, retries=0)
            local = np.random.RandomState(100 + k)
            for j in range(REQUESTS_PER_WORKER):
                rid = f"smoke-{k}-{j}"
                x = local.randn(1 + j % 4, 4).astype(np.float32)
                try:
                    full = client.predict_full(x, request_id=rid,
                                               timeout_s=30.0)
                    echoes.append((rid, full["request_id"]))
                except Exception as exc:  # noqa: BLE001
                    errors.append((rid, exc))
            client.close()

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(WORKERS)]
        for t in threads:
            t.start()

        # chaos: hard-kill one replica mid-burst, then restart it on the
        # same port — the router must absorb both transitions
        time.sleep(0.2)
        victim_port = ports[0]
        procs[victim_port].send_signal(signal.SIGKILL)
        procs[victim_port].wait()
        print(f"killed replica :{victim_port} (SIGKILL)", flush=True)
        time.sleep(0.5)
        procs[victim_port] = spawn_replica(victim_port)
        print(f"restarted replica :{victim_port}", flush=True)

        for t in threads:
            t.join(timeout=120)

        total = WORKERS * REQUESTS_PER_WORKER
        assert not errors, (f"{len(errors)} client-visible failures, "
                            f"first: {errors[:3]}")
        assert len(echoes) == total, (len(echoes), total)
        assert all(sent == got for sent, got in echoes), \
            "a response lost its X-Request-Id"

        # the restarted replica must rejoin the rotation
        probe = ServingClient(router.url)
        deadline = time.time() + 30
        health = probe.healthz()
        while health["healthy_replicas"] < N_REPLICAS \
                and time.time() < deadline:
            time.sleep(0.2)
            health = probe.healthz()
        assert health["healthy_replicas"] == N_REPLICAS, health
        counters = probe.metrics()["counters"]
        probe.close()
        if tracker is not None:
            tracker.assert_clean()
            print("racecheck: zero data races across the chaos burst",
                  flush=True)
        print(f"fleet-smoke OK: {total}/{total} requests served with zero "
              f"failures through kill+restart "
              f"(rerouted={counters.get('router/rerouted', 0):.0f}, "
              f"healthy_replicas={health['healthy_replicas']})", flush=True)
        clean = True
    finally:
        if tracker is not None:
            tracker.uninstall()
        if router is not None:
            router.stop()
        # balance is only meaningful after router.stop() took the replica
        # gauges down; skip the assert when the smoke already failed so the
        # original error isn't shadowed by the leaks it caused
        if retracker is not None:
            retracker.uninstall()
            if clean:
                retracker.assert_balanced()
                print(f"restrack: zero unbalanced resources "
                      f"({retracker.acquired} acquired, "
                      f"{retracker.released} released)", flush=True)
        for proc in procs.values():
            if proc.poll() is None:
                proc.terminate()
        for proc in procs.values():
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--replica", type=int, metavar="PORT",
                        help="internal: run one replica process on PORT")
    ns = parser.parse_args()
    if ns.replica is not None:
        run_replica(ns.replica)
    else:
        main()
