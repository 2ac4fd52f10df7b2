"""Distributed-tracing smoke: one hedged generate, one waterfall, one crash.

Run via ``make trace-smoke`` (or directly). The script

1. spawns two real replica *processes* (re-invoking itself with
   ``--replica PORT``), each an :class:`InferenceServer` hosting a
   :class:`DecodeEngine` behind a :class:`ContinuousBatcher`, flight
   recorder armed; the first replica gets a chaos fault — its prefill
   stalls 1.2s, the straggler a hedge must race around;
2. starts a :class:`RouterServer` with hedging in front and sends ONE
   ``/v1/generate`` with a client-minted ``traceparent``;
3. fetches the assembled trace from the router (``GET /traces/<id>``)
   and prints the cross-process waterfall: router dispatch spans with
   the hedge loser labeled, both replicas' queue/admission/decode-tick
   spans, all on one wall-clock timeline — asserting it is a SINGLE
   trace spanning three processes;
4. SIGKILLs the slow replica with a second traced request provably in
   flight (its flight-recorder ``begin`` line already on disk), then
   harvests the flight file and prints the postmortem: the dead
   process's identity and the exact in-flight trace ids it took down.

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

from sparkflow_tpu.obs import TraceCollector, harvest_flight
from sparkflow_tpu.obs.spans import TraceContext
from sparkflow_tpu.serving import RouterServer, ServingClient

VOCAB = 97
CHAOS_DELAY_S = 1.2
HEDGE_DELAY_MS = 150.0


class _ChaosPrefill:
    """DecodeEngine wrapper whose prefill stalls — the chaos-delayed
    straggler a hedge must race around."""

    def __init__(self, engine, delay_s):
        self._engine = engine
        self.delay_s = delay_s

    def prefill(self, *args, **kwargs):
        time.sleep(self.delay_s)
        return self._engine.prefill(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._engine, name)


class _EchoEngine:
    """Keeps the predict plane constructible; this smoke only generates."""
    max_batch = 4

    def predict(self, x):
        return x


def run_replica(port: int, flight_dir: str, chaos_delay_s: float) -> None:
    import jax

    from sparkflow_tpu.models.registry import (build_registry_spec,
                                               model_from_json)
    from sparkflow_tpu.resilience.lifecycle import ServerState
    from sparkflow_tpu.serving import (ContinuousBatcher, DecodeEngine,
                                       InferenceServer)

    spec = build_registry_spec("transformer_lm", vocab_size=VOCAB, hidden=32,
                               num_layers=2, num_heads=4, mlp_dim=64,
                               max_len=64, dropout=0.0)
    model = model_from_json(spec)
    params = model.init(jax.random.PRNGKey(0))
    engine = DecodeEngine(model, params, num_slots=4, page_size=8, seed=0,
                          prefill_chunk=8)
    if chaos_delay_s:
        engine = _ChaosPrefill(engine, chaos_delay_s)
    server = InferenceServer(_EchoEngine(), port=port,
                             generate_batcher=ContinuousBatcher(
                                 engine, max_queue=64),
                             flight_dir=flight_dir, drain_timeout_s=60.0)
    server.start()
    # hedge losers get their sockets torn down by the router; that is the
    # point of hedging, not an error worth a traceback per loss
    server._httpd.handle_error = lambda *a: None
    server.install_signal_handlers()
    print(f"replica up on {server.url}"
          + (f" (chaos: prefill +{chaos_delay_s}s)" if chaos_delay_s else ""),
          flush=True)
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


def spawn_replica(port: int, flight_dir: str,
                  chaos_delay_s: float = 0.0) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, __file__, "--replica", str(port),
         "--flight-dir", flight_dir, "--chaos-delay-s", str(chaos_delay_s)])


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
    raise TimeoutError(f"replica at {url} never became healthy")


def main() -> None:
    flight_dir = tempfile.mkdtemp(prefix="trace-smoke-")
    slow_port, fast_port = free_ports(2)
    slow_url = f"http://127.0.0.1:{slow_port}"
    fast_url = f"http://127.0.0.1:{fast_port}"
    procs = {
        slow_port: spawn_replica(slow_port, flight_dir, CHAOS_DELAY_S),
        fast_port: spawn_replica(fast_port, flight_dir),
    }
    router = None
    try:
        wait_healthy(slow_url)
        wait_healthy(fast_url)
        # trace_sample=0.0: nothing is head-sampled, so the trace below is
        # kept purely by the tail-sampler's "hedged" rule
        router = RouterServer([slow_url, fast_url], probe_interval_s=0.5,
                              hedge=True, hedge_delay_ms=HEDGE_DELAY_MS,
                              dispatch_retries=1, trace_sample=0.0).start()
        print(f"router up on {router.url} fronting 2 replicas "
              f"(hedge after {HEDGE_DELAY_MS:.0f}ms)", flush=True)

        # -- one hedged request, one trace -------------------------------
        ctx = TraceContext.mint()
        client = ServingClient(router.url, retries=0)
        out = client.generate([1, 2, 3, 4], max_new_tokens=6,
                              traceparent=ctx, request_id="trace-smoke-1",
                              timeout_s=60.0)
        assert out["num_tokens"] == 6, out
        print(f"hedged generate OK ({out['num_tokens']} tokens), "
              f"trace_id={ctx.trace_id}", flush=True)

        # read-time re-assembly settles the loser leg's label once the
        # chaos-delayed replica finally finishes
        deadline = time.time() + 30.0
        trace = None
        while time.time() < deadline:
            trace = client._request(f"/traces/{ctx.trace_id}")
            outcomes = sorted(
                (s.get("args") or {}).get("outcome", "")
                for s in trace["spans"] if s["name"] == "router/dispatch")
            if outcomes == ["loser", "winner"]:
                break
            time.sleep(0.3)
        assert trace is not None and outcomes == ["loser", "winner"], \
            f"hedge outcomes never settled: {outcomes}"
        assert trace["trace_id"] == ctx.trace_id
        assert trace["reason"] == "hedged", trace["reason"]
        procs_in_trace = {s["process"] for s in trace["spans"]}
        assert len(procs_in_trace) == 3, \
            f"expected router + 2 replicas on one timeline: {procs_in_trace}"
        names = {s["name"] for s in trace["spans"]}
        for required in ("router/request", "router/dispatch",
                         "serving/request", "serving/decode_admit",
                         "serving/decode_tick"):
            assert required in names, f"missing {required}: {sorted(names)}"
        ts = [s["ts"] for s in trace["spans"]]
        assert ts == sorted(ts), "waterfall is not wall-clock ordered"
        print(f"\nassembled ONE trace across {len(procs_in_trace)} processes "
              f"({len(trace['spans'])} spans, {trace['duration_ms']:.0f}ms):\n",
              flush=True)
        print(TraceCollector.waterfall(trace), flush=True)

        # -- crash flight recorder ---------------------------------------
        # a second traced request straight at the slow replica; SIGKILL it
        # with the request provably in flight (begin line on disk), then
        # read the postmortem out of the flight file
        ctx_dead = TraceContext.mint()
        flight_path = os.path.join(flight_dir, f"replica-{slow_port}.jsonl")

        def doomed():
            c = ServingClient(slow_url, retries=0)
            try:
                c.generate([5, 6, 7], max_new_tokens=4, traceparent=ctx_dead,
                           request_id="trace-smoke-doomed", timeout_s=5.0)
            except Exception:
                pass  # the whole point: this replica dies mid-request
            c.close()

        rider = threading.Thread(target=doomed)
        rider.start()
        deadline = time.time() + 15.0
        while time.time() < deadline:
            try:
                with open(flight_path) as f:
                    if ctx_dead.trace_id in f.read():
                        break
            except OSError:
                pass
            time.sleep(0.05)
        procs[slow_port].send_signal(signal.SIGKILL)
        procs[slow_port].wait()
        rider.join(timeout=30)
        print(f"\nSIGKILLed slow replica :{slow_port} mid-request", flush=True)

        report = harvest_flight(flight_path)
        assert report is not None, f"no flight evidence at {flight_path}"
        assert not report["dumped"], "SIGKILL must not have run a dump"
        assert ctx_dead.trace_id in report["inflight_trace_ids"], report
        print(f"flight harvest: process {report['process']} died with "
              f"{len(report['inflight_trace_ids'])} request(s) in flight: "
              f"{report['inflight_trace_ids']}", flush=True)

        client.close()
        print(f"\ntrace-smoke OK: one hedged generate assembled into a "
              f"single {len(procs_in_trace)}-process waterfall (loser "
              f"labeled), and a SIGKILL postmortem named the in-flight "
              f"trace id", flush=True)
    finally:
        if router is not None:
            router.stop()
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
    parser.add_argument("--flight-dir", default="",
                        help="internal: flight-recorder directory")
    parser.add_argument("--chaos-delay-s", type=float, default=0.0,
                        help="internal: stall this replica's prefill")
    ns = parser.parse_args()
    if ns.replica is not None:
        run_replica(ns.replica, ns.flight_dir, ns.chaos_delay_s)
    else:
        main()
