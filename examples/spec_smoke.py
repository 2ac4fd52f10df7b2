"""Speculative-decode serving smoke: a real server subprocess with spec on.

Run via ``make spec-smoke`` (or directly). The script

1. spawns one server *process* (re-invoking itself with ``--server PORT``)
   hosting a :class:`DecodeEngine` with **speculative decoding enabled**
   (self-speculation, ``spec_k=3``) on top of shared-prefix caching AND
   chunked prefill, behind a :class:`ContinuousBatcher` with SIGTERM drain
   handlers installed;
2. drives a concurrent burst of mixed-length greedy ``/v1/generate``
   requests — short and long prompts (some crossing the chunked-prefill
   threshold), short and long budgets — through plain
   :class:`ServingClient`\\ s;
3. asserts every response is **token-identical** to a locally rebuilt
   engine with speculation OFF (and sharing off, chunking off — the
   plainest decode path there is), i.e. speculation changed the schedule,
   not the text;
4. checks ``/healthz``'s decode block reports **zero** steady-state
   retraces and a live ``spec_accept_rate``;
5. SIGTERMs the server mid-flight and asserts the drain is clean:
   the in-flight generation completes and the process exits 0.

Everything runs on CPU (``JAX_PLATFORMS=cpu``) in under a minute.
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

import jax

from sparkflow_tpu.models.registry import build_registry_spec, model_from_json
from sparkflow_tpu.serving import (ContinuousBatcher, DecodeEngine,
                                   InferenceServer, ServingClient)

VOCAB = 97
WORKERS = 4
REQUESTS_PER_WORKER = 4
SPEC_K = 3


def build_lm():
    spec = build_registry_spec("transformer_lm", vocab_size=VOCAB, hidden=32,
                               num_layers=2, num_heads=4, mlp_dim=64,
                               max_len=64, dropout=0.0)
    model = model_from_json(spec)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def make_generate_batcher() -> ContinuousBatcher:
    model, params = build_lm()
    engine = DecodeEngine(model, params, num_slots=4, page_size=8, seed=0,
                          prefill_chunk=8, spec_k=SPEC_K)
    return ContinuousBatcher(engine, max_queue=64)


class _EchoEngine:
    """Keeps the predict plane constructible; this smoke only generates."""
    max_batch = 4

    def predict(self, x):
        return x


def run_server(port: int) -> None:
    from sparkflow_tpu.resilience.lifecycle import ServerState
    server = InferenceServer(_EchoEngine(), port=port,
                             generate_batcher=make_generate_batcher(),
                             drain_timeout_s=60.0)
    server.start()
    server.install_signal_handlers()
    print(f"spec decode server up on {server.url}", flush=True)
    while server.lifecycle.state in (ServerState.STARTING,
                                     ServerState.SERVING):
        time.sleep(0.2)
    server.stop()
    print("spec decode server drained and stopped", flush=True)


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
    proc = subprocess.Popen([sys.executable, __file__, "--server",
                             str(port)])
    errors = []
    results = {}
    try:
        wait_healthy(url)

        # mixed-length greedy burst: prompts 2..25 tokens (the long ones
        # cross the chunked-prefill threshold and, via repeats, hit the
        # prefix cache), budgets 3..17 — all greedy so every token is
        # checkable against the spec-off reference
        def worker(k: int) -> None:
            client = ServingClient(url, timeout=120, retries=2)
            for j in range(REQUESTS_PER_WORKER):
                rid = f"spec-{k}-{j}"
                n = 2 + (9 * k + 5 * j) % 24
                prompt = [(i * 13 + k + j) % VOCAB for i in range(n)]
                budget = 3 + (5 * k + j) % 15
                try:
                    r = client.generate(prompt, max_new_tokens=budget,
                                        temperature=0.0, request_id=rid)
                    if r["num_tokens"] != budget or \
                            r["finish_reason"] != "length":
                        errors.append((rid, f"bad completion: {r}"))
                    results[(tuple(prompt), budget)] = r["tokens"]
                except Exception as exc:  # noqa: BLE001
                    errors.append((rid, exc))
            client.close()

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(WORKERS)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        elapsed = time.time() - t0
        assert not errors, (f"{len(errors)} failures, first: {errors[:3]}")

        # a repeated-prompt wave: identical prompts re-submitted so the
        # server's prefix cache serves them as hits while speculation runs
        client = ServingClient(url, timeout=120)
        replio = list(results.items())[:4]
        for (prompt, budget), want in replio:
            again = client.generate(list(prompt), max_new_tokens=budget,
                                    temperature=0.0)
            assert again["tokens"] == want, (again["tokens"], want)

        health = client.healthz()
        dec = health["decode"]
        eng_stats = dec["engine"]
        assert eng_stats["steady_traces"] == 0, \
            f"speculative decode retraced after warmup: {eng_stats}"
        assert eng_stats["spec"]["enabled"] and eng_stats["spec"]["steps"] > 0
        assert "spec_accept_rate" in dec, \
            f"/healthz decode block lacks spec_accept_rate: {dec}"
        assert 0.0 <= dec["spec_accept_rate"] <= 1.0
        hits = eng_stats["kv"]["prefix_hits"]
        assert hits > 0, f"replayed prompts produced no prefix hits: {eng_stats}"

        # clean SIGTERM drain: in-flight request survives, process exits 0
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
        # backend: a chip belongs to one process at a time.
        # token-identical parity vs the plainest possible engine: spec off,
        # sharing off, chunking off — speculation must not change the text
        model, params = build_lm()
        ref_cb = ContinuousBatcher(
            DecodeEngine(model, params, num_slots=4, page_size=8, seed=0,
                         prefix_cache=False), max_queue=64)
        try:
            for (prompt, budget), want in results.items():
                r = ref_cb.generate(list(prompt), max_new_tokens=budget,
                                    timeout=120)
                assert r["tokens"] == want, (prompt[:4], r["tokens"], want)
        finally:
            ref_cb.close()
        total = WORKERS * REQUESTS_PER_WORKER
        print(f"spec-smoke OK: {total} mixed-length speculative generations "
              f"in {elapsed:.1f}s (k={SPEC_K}, accept_rate="
              f"{dec['spec_accept_rate']:.2f}, {hits} prefix hits), every "
              f"token identical to spec-off decode, 0 steady-state "
              f"retraces, clean SIGTERM drain", flush=True)
    finally:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--server", type=int, metavar="PORT",
                        help="internal: run the spec decode server on PORT")
    ns = parser.parse_args()
    if ns.server is not None:
        run_server(ns.server)
    else:
        main()
