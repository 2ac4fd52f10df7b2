"""Live weight-publication smoke: hot-swap a real serving subprocess.

Run via ``make swap-smoke`` (or directly). The script

1. spawns one server *process* (re-invoking itself with ``--server PORT
   --store DIR``) hosting a :class:`DecodeEngine` behind a
   :class:`ContinuousBatcher`, with a :class:`WeightWatcher` polling a
   shared :class:`WeightStore` directory and SIGTERM drain handlers
   installed;
2. drives a sustained concurrent burst of greedy ``/v1/generate``
   requests while a "trainer" (this driver) publishes **two** weight
   sets mid-burst: one good version, then one that is corrupted on disk
   after commit (``faults.corrupt_latest_weights``);
3. asserts zero client-visible failures across the whole burst, that
   ``/healthz`` reports the ``serving_version`` flipping 0 -> 1 exactly
   once (the corrupt version 2 never takes traffic; the watcher reports
   it under ``pull_failures`` / ``failed_versions`` and keeps last-good),
   and that post-swap greedy output is token-identical to a local engine
   cold-started on the published weights;
4. SIGTERMs the server with a generation in flight and asserts the drain
   is clean: the in-flight request completes and the process exits 0.

Everything runs on CPU (``JAX_PLATFORMS=cpu``) in under a minute.
"""

import argparse
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from sparkflow_tpu.models.registry import build_registry_spec, model_from_json
from sparkflow_tpu.resilience import faults
from sparkflow_tpu.serving import (ContinuousBatcher, DecodeEngine,
                                   InferenceServer, ServingClient)
from sparkflow_tpu.serving.weightstore import WeightStore, WeightWatcher

VOCAB = 97
WORKERS = 4
REQUESTS_PER_WORKER = 6


def make_model():
    spec = build_registry_spec("transformer_lm", vocab_size=VOCAB, hidden=32,
                               num_layers=2, num_heads=4, mlp_dim=64,
                               max_len=64, dropout=0.0)
    return model_from_json(spec)


def host_params(model, seed: int):
    """A weight tree made with numpy alone: the publishing side of this
    smoke stands for a trainer on another machine and must not touch the
    backend its server child is using (a chip belongs to one process)."""
    rs = np.random.RandomState(seed)
    return {layer: {name: (0.05 * rs.standard_normal(shape)
                           ).astype(np.float32)
                    for name, (shape, _init) in leaves.items()}
            for layer, leaves in model.param_specs().items()}


class _EchoEngine:
    """Keeps the predict plane constructible; this smoke only generates."""
    max_batch = 4

    def predict(self, x):
        return x


def run_server(port: int, store_dir: str) -> None:
    from sparkflow_tpu.resilience.lifecycle import ServerState
    model = make_model()
    params = model.init(jax.random.PRNGKey(0))
    engine = DecodeEngine(model, params, num_slots=4, page_size=8, seed=0,
                          prefill_chunk=8)
    watcher = WeightWatcher(WeightStore(store_dir), [engine],
                            poll_interval_s=0.05)
    server = InferenceServer(_EchoEngine(), port=port,
                             generate_batcher=ContinuousBatcher(
                                 engine, max_queue=64),
                             weight_watcher=watcher,
                             drain_timeout_s=60.0)
    server.start()
    server.install_signal_handlers()
    print(f"swap server up on {server.url}", flush=True)
    while server.lifecycle.state in (ServerState.STARTING,
                                     ServerState.SERVING):
        time.sleep(0.2)
    server.stop()
    print("swap server drained and stopped", flush=True)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wait_healthy(url: str, timeout_s: float = 120.0) -> None:
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
    raise TimeoutError(f"server at {url} never became healthy")


def main() -> None:
    port = free_port()
    url = f"http://127.0.0.1:{port}"
    store_dir = tempfile.mkdtemp(prefix="swap_smoke_store_")
    store = WeightStore(store_dir)
    model = make_model()
    good_params = host_params(model, 1)
    proc = subprocess.Popen([sys.executable, __file__, "--server",
                             str(port), "--store", store_dir])
    errors = []
    versions_seen = []  # serving_version samples, in order
    stop_burst = threading.Event()
    done = [0]
    try:
        wait_healthy(url)

        # sustained greedy burst: the swap must land inside it without a
        # single failed or malformed response
        def worker(k: int) -> None:
            client = ServingClient(url, timeout=120, retries=0)
            for j in range(REQUESTS_PER_WORKER):
                rid = f"swap-{k}-{j}"
                n = 2 + (5 * k + 3 * j) % 17
                prompt = [(i * 13 + k + j) % VOCAB for i in range(n)]
                budget = 3 + (7 * k + j) % 12
                try:
                    r = client.generate(prompt, max_new_tokens=budget,
                                        temperature=0.0, request_id=rid)
                    if r["num_tokens"] != budget or \
                            r["finish_reason"] != "length":
                        errors.append((rid, f"bad completion: {r}"))
                except Exception as exc:  # noqa: BLE001
                    errors.append((rid, exc))
                done[0] += 1
            client.close()

        # healthz sampler: every observed serving_version, in order, so a
        # double flip (0->1->2 or a bounce back to 0) cannot hide between
        # explicit checks
        def sampler() -> None:
            c = ServingClient(url, timeout=10, retries=0)
            while not stop_burst.is_set():
                try:
                    w = c.healthz(timeout_s=2.0).get("weights")
                    if w is not None:
                        versions_seen.append(int(w["serving_version"]))
                except Exception:
                    pass
                time.sleep(0.02)
            c.close()

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(WORKERS)]
        monitor = threading.Thread(target=sampler)
        monitor.start()
        for t in threads:
            t.start()

        # publish the GOOD version once the burst is genuinely in flight
        while done[0] < (WORKERS * REQUESTS_PER_WORKER) // 4:
            time.sleep(0.02)
        v_good = store.publish(good_params)
        assert v_good == 1, v_good

        # wait for the replica to pull + swap at a drained boundary
        client = ServingClient(url, timeout=120, retries=0)
        deadline = time.time() + 60
        while time.time() < deadline:
            w = client.healthz()["weights"]
            if w["serving_version"] == v_good:
                break
            time.sleep(0.05)
        else:
            raise AssertionError(f"server never swapped to v{v_good}: {w}")

        # publish a SECOND version, then corrupt it on disk the way a
        # crash or bit-rot would — the replica must reject it on checksum,
        # keep serving v1, and never surface an error to clients
        v_bad = store.publish(host_params(model, 2))
        assert v_bad == 2, v_bad
        faults.corrupt_latest_weights(store_dir, mode="flip")
        deadline = time.time() + 60
        while time.time() < deadline:
            w = client.healthz()["weights"]
            if w["pull_failures"] > 0:
                break
            time.sleep(0.05)
        else:
            raise AssertionError(f"corrupt v2 never hit pull_failures: {w}")
        assert v_bad in w["failed_versions"], w
        assert w["serving_version"] == v_good, w

        for t in threads:
            t.join(timeout=300)
        stop_burst.set()
        monitor.join(timeout=30)

        total = WORKERS * REQUESTS_PER_WORKER
        assert not errors, (f"{len(errors)} client-visible failures, "
                            f"first: {errors[:3]}")
        assert done[0] == total, (done[0], total)

        # the version flipped exactly once: the ordered samples must be a
        # run of 0s followed by a run of 1s (no bounce, no corrupt v2)
        w = client.healthz()["weights"]
        assert w["serving_version"] == v_good, w
        flips = sum(1 for a, b in zip(versions_seen, versions_seen[1:])
                    if a != b)
        assert flips == 1, \
            f"serving_version flipped {flips} times: {versions_seen}"
        assert set(versions_seen) == {0, v_good}, versions_seen

        # post-swap greedy tokens, compared below with a cold engine
        prompt = [3, 1, 4, 1, 5]
        got = client.generate(prompt, max_new_tokens=8, temperature=0.0)

        # clean SIGTERM drain with a generation in flight
        late = {}

        def slow_request() -> None:
            c = ServingClient(url, timeout=120, retries=0)
            try:
                late["result"] = c.generate([1, 2, 3], max_new_tokens=30,
                                            request_id="drain-rider")
            except Exception as exc:  # noqa: BLE001
                late["error"] = exc
            c.close()

        rider = threading.Thread(target=slow_request)
        rider.start()
        time.sleep(0.3)  # let it get admitted
        proc.send_signal(signal.SIGTERM)
        rider.join(timeout=120)
        client.close()
        assert "result" in late, f"in-flight generation died: {late}"
        assert late["result"]["num_tokens"] == 30

        proc.wait(timeout=60)
        assert proc.returncode == 0, \
            f"server exited {proc.returncode} on SIGTERM drain"

        # Only now, with the server child gone, does this process touch a
        # backend. Post-swap greedy parity: the server must have emitted the
        # same tokens as a local engine cold-started on the published good
        # weights
        ref = ContinuousBatcher(
            DecodeEngine(model, good_params, num_slots=4, page_size=8,
                         seed=0), max_queue=64)
        try:
            want = ref.generate(prompt, max_new_tokens=8, timeout=120)
            assert got["tokens"] == want["tokens"], \
                (got["tokens"], want["tokens"])
        finally:
            ref.close()
        print(f"swap-smoke OK: {total} generations with 0 failures across "
              f"a live publish (v0 -> v{v_good}, exactly 1 healthz flip), "
              f"corrupt v{v_bad} rejected on checksum with last-good kept "
              f"({w['pull_failures']} pull failures), post-swap greedy "
              f"parity vs cold engine, clean SIGTERM drain", flush=True)
    finally:
        stop_burst.set()
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--server", type=int, metavar="PORT",
                        help="internal: run the swap server on PORT")
    parser.add_argument("--store", type=str, metavar="DIR",
                        help="internal: weight store directory to watch")
    ns = parser.parse_args()
    if ns.server is not None:
        run_server(ns.server, ns.store)
    else:
        main()
