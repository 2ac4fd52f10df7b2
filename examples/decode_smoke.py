"""Decode-serving smoke: a real InferenceServer subprocess generating text.

Run via ``make decode-smoke`` (or directly). The script

1. spawns one server *process* (re-invoking itself with ``--server PORT``)
   hosting a :class:`DecodeEngine` (paged KV cache + pallas paged attention
   + AOT prefill/decode) behind a :class:`ContinuousBatcher`, with SIGTERM
   drain handlers installed;
2. drives a concurrent burst of mixed-length ``/v1/generate`` requests —
   short and long prompts, short and long generation budgets, greedy and
   seeded sampling — through plain :class:`ServingClient`\\ s;
3. asserts every response echoed its originating ``X-Request-Id``, returned
   the requested token budget (``finish_reason == "length"``), and that the
   greedy requests are deterministic across repeats;
4. fires a shared-prefix burst (every client the same 24-token system
   prompt, distinct tails) and asserts the server's prefix cache actually
   shared pages (hit rate > 0) AND that every response is token-identical
   to a locally rebuilt engine with sharing disabled and no chunking;
5. checks the server's ``/healthz`` decode block reports **zero**
   steady-state retraces after the bursts;
6. SIGTERMs the server mid-burst of a second wave and asserts the drain is
   clean: in-flight generations complete, the process exits 0.

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
                                   InferenceServer, ServingClient,
                                   ServingError)

VOCAB = 97
WORKERS = 4
REQUESTS_PER_WORKER = 5


def make_generate_batcher() -> ContinuousBatcher:
    spec = build_registry_spec("transformer_lm", vocab_size=VOCAB, hidden=32,
                               num_layers=2, num_heads=4, mlp_dim=64,
                               max_len=64, dropout=0.0)
    model = model_from_json(spec)
    params = model.init(jax.random.PRNGKey(0))
    engine = DecodeEngine(model, params, num_slots=4, page_size=8, seed=0,
                          prefill_chunk=8)
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
    print(f"decode server up on {server.url}", flush=True)
    while server.lifecycle.state in (ServerState.STARTING,
                                     ServerState.SERVING):
        time.sleep(0.2)
    server.stop()
    print("decode server drained and stopped", flush=True)


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
    errors, echoes, greedy = [], [], {}
    try:
        wait_healthy(url)

        # mixed-length burst: prompts 2..24 tokens, budgets 3..17 tokens,
        # greedy and seeded-sampled requests interleaved
        def worker(k: int) -> None:
            client = ServingClient(url, timeout=120, retries=2)
            for j in range(REQUESTS_PER_WORKER):
                rid = f"decode-{k}-{j}"
                n = 2 + (7 * k + 3 * j) % 23
                prompt = [(i * 13 + k + j) % VOCAB for i in range(n)]
                budget = 3 + (5 * k + j) % 15
                greedy_req = (k + j) % 2 == 0
                try:
                    r = client.generate(
                        prompt, max_new_tokens=budget,
                        temperature=0.0 if greedy_req else 0.8,
                        top_k=0 if greedy_req else 16,
                        seed=None if greedy_req else 1000 + k,
                        request_id=rid)
                    echoes.append((rid, r["request_id"],
                                   r["x_request_id_header"]))
                    if r["num_tokens"] != budget or \
                            r["finish_reason"] != "length":
                        errors.append((rid, f"bad completion: {r}"))
                    if greedy_req:
                        greedy[(tuple(prompt), budget)] = r["tokens"]
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

        total = WORKERS * REQUESTS_PER_WORKER
        assert not errors, (f"{len(errors)} failures, first: {errors[:3]}")
        assert len(echoes) == total, (len(echoes), total)
        assert all(rid == body == hdr for rid, body, hdr in echoes), \
            "a response lost its X-Request-Id"

        # greedy decode is deterministic: replay one request, same tokens
        client = ServingClient(url, timeout=120)
        (prompt, budget), want = next(iter(greedy.items()))
        again = client.generate(list(prompt), max_new_tokens=budget,
                                temperature=0.0)
        assert again["tokens"] == want, (again["tokens"], want)

        # shared-prefix burst: every client sends the same 24-token system
        # prompt with a distinct 4-token tail — the server's prefix cache
        # must share the system pages (hit rate > 0) and its chunked
        # prefill must split the cold 28-token prompts, all while staying
        # greedy-exact (checked against a sharing-off engine below)
        SYS = [(i * 7 + 5) % VOCAB for i in range(24)]
        shared_results = {}

        def shared_worker(k: int) -> None:
            c = ServingClient(url, timeout=120, retries=2)
            for j in range(3):
                tail = [(k * 11 + j * 3 + i + 1) % VOCAB for i in range(4)]
                try:
                    r = c.generate(SYS + tail, max_new_tokens=6,
                                   temperature=0.0)
                    shared_results[tuple(SYS + tail)] = r["tokens"]
                except Exception as exc:  # noqa: BLE001
                    errors.append((f"shared-{k}-{j}", exc))
            c.close()

        sthreads = [threading.Thread(target=shared_worker, args=(k,))
                    for k in range(WORKERS)]
        for t in sthreads:
            t.start()
        for t in sthreads:
            t.join(timeout=300)
        assert not errors, (f"{len(errors)} shared-prefix failures, "
                            f"first: {errors[:3]}")

        health = client.healthz()
        dec = health["decode"]["engine"]
        assert dec["steady_traces"] == 0, \
            f"decode retraced after warmup: {dec}"
        kv = dec["kv"]
        assert kv["prefix_hits"] > 0, \
            f"shared-prefix burst produced no prefix hits: {kv}"

        # clean SIGTERM drain: start a slow request, signal mid-flight,
        # and require BOTH a completed in-flight generation and 503s for
        # latecomers, then exit code 0
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
        assert "result" in late, f"in-flight generation died: {late}"
        assert late["result"]["num_tokens"] == 30

        # after the drain begins, new requests must be shed with 503
        try:
            deadline = time.time() + 30
            shed = False
            while time.time() < deadline and not shed:
                try:
                    client.generate([5], max_new_tokens=2, retries=0,
                                    timeout_s=5.0)
                    time.sleep(0.1)
                except ServingError as exc:
                    assert exc.status == 503, exc
                    shed = True
                except OSError:
                    shed = True  # socket already down: drain completed
            assert shed, "draining server kept accepting new generates"
        finally:
            client.close()

        proc.wait(timeout=60)
        assert proc.returncode == 0, \
            f"server exited {proc.returncode} on SIGTERM drain"

        # Only now, with the server child gone, does this process touch a
        # backend: a chip belongs to one process at a time.
        # greedy parity with sharing disabled: the same deterministic
        # engine rebuilt locally with prefix_cache off and no chunking
        # must emit identical tokens for every shared-prefix request
        spec = build_registry_spec("transformer_lm", vocab_size=VOCAB,
                                   hidden=32, num_layers=2, num_heads=4,
                                   mlp_dim=64, max_len=64, dropout=0.0)
        ref_model = model_from_json(spec)
        ref_params = ref_model.init(jax.random.PRNGKey(0))
        ref_cb = ContinuousBatcher(
            DecodeEngine(ref_model, ref_params, num_slots=4, page_size=8,
                         seed=0, prefix_cache=False), max_queue=64)
        try:
            for sp, want_toks in shared_results.items():
                r = ref_cb.generate(list(sp), max_new_tokens=6, timeout=120)
                assert r["tokens"] == want_toks, \
                    (sp[-4:], r["tokens"], want_toks)
        finally:
            ref_cb.close()
        toks = sum(3 + (5 * k + j) % 15 for k in range(WORKERS)
                   for j in range(REQUESTS_PER_WORKER))
        print(f"decode-smoke OK: {total} mixed-length generations "
              f"({toks} tokens in {elapsed:.1f}s), every X-Request-Id "
              f"echoed, {len(shared_results)} shared-prefix generations "
              f"({kv['prefix_hits']} prefix hits) greedy-exact vs sharing "
              f"off, 0 steady-state retraces, clean SIGTERM drain",
              flush=True)
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
                        help="internal: run the decode server on PORT")
    ns = parser.parse_args()
    if ns.server is not None:
        run_server(ns.server)
    else:
        main()
