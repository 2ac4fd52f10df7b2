"""Headline benchmark: MNIST CNN training throughput (examples/sec) on TPU.

Config matches BASELINE.md's primary metric — the reference's
``examples/cnn_example.py`` model trained via the framework — against the
measured single-node Hogwild-proxy baseline in ``BASELINE_MEASURED.json``
(see ``bench_baseline.py``; the reference publishes no numbers of its own).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _load_baseline():
    """Current baseline ex/s from BASELINE_MEASURED.json, or None."""
    path = os.path.join(_HERE, "BASELINE_MEASURED.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)["baseline_examples_per_sec"]


def main():
    from sparkflow_tpu.utils.hw import enable_compilation_cache

    # persistent XLA cache: repeat bench invocations skip the compile
    enable_compilation_cache()

    import jax

    import sparkflow_tpu.nn as nn
    from sparkflow_tpu.graph_utils import build_graph
    from sparkflow_tpu.trainer import Trainer
    from sparkflow_tpu.parallel.mesh import default_mesh

    quick = "--quick" in sys.argv

    def cnn_model():
        x = nn.placeholder([None, 784], name="x")
        y = nn.placeholder([None, 10], name="y")
        xr = nn.reshape(x, [-1, 28, 28, 1])
        c1 = nn.conv2d(xr, 32, 5, activation="relu")
        p1 = nn.max_pooling2d(c1, 2, 2)
        c2 = nn.conv2d(p1, 64, 3, activation="relu")
        p2 = nn.max_pooling2d(c2, 2, 2)
        out = nn.dense(nn.flatten(p2), 10, name="out")
        nn.softmax_cross_entropy(y, out)

    mg = build_graph(cnn_model)

    n = 4096 if quick else 16384
    rs = np.random.RandomState(0)
    x = rs.rand(n, 784).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, n)]

    platform = jax.devices()[0].platform
    # bf16 compute on TPU (f32 accumulation stays on); f32 elsewhere
    compute_dtype = "bfloat16" if platform == "tpu" else None

    iters = 2 if quick else 6
    trainer = Trainer(mg, "x:0", "y:0", optimizer="adam",
                      optimizer_options={"learning_rate": 1e-3},
                      mini_batch_size=1024, shuffle_per_iter=True,
                      iters=iters, mesh=default_mesh(),
                      compute_dtype=compute_dtype)

    # warmup fit compiles the SAME fused multi-epoch program the measured
    # fit reuses (the whole fit is one device dispatch — see
    # core.make_multi_epoch_fn); measured run starts from its params
    trainer.fit(x, y)

    # median-of-3: single-run headlines are fragile, so the protocol lives
    # in-code
    runs = 1 if quick else 3
    eps_runs = sorted(
        trainer.fit(x, y, init_params=trainer.params).examples_per_sec
        for _ in range(runs))
    eps = eps_runs[len(eps_runs) // 2]

    base = _load_baseline()
    vs_baseline = round(eps / base, 2) if base else None

    out = {
        "metric": "mnist_cnn_examples_per_sec",
        "value": round(eps, 1),
        "unit": "examples/sec",
        "vs_baseline": vs_baseline,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
    }
    if runs > 1:
        out["runs"] = [round(e, 1) for e in eps_runs]
    # MFU accounting: XLA's own FLOPs count for one train step (the CNN is
    # pure XLA — no pallas custom calls to undercount), times steps/sec,
    # against the chip's bf16 peak
    from sparkflow_tpu.utils.flops import (device_peak_flops, mfu,
                                           train_step_flops)
    step_fl = train_step_flops(trainer.model, "x:0", "y:0",
                               trainer.optimizer, x[:1024], y[:1024])
    if step_fl:
        fps = (eps / 1024.0) * step_fl
        out["tflops_per_sec"] = round(fps / 1e12, 3)
        u = mfu(fps, device_peak_flops())
        if u is not None:
            out["mfu"] = round(u, 4)
    print(json.dumps(out))


def span_overhead_main():
    """Micro-bench for the obs layer: run the same jitted train step in a
    tight loop with and without ``obs.span`` instrumentation and report the
    relative overhead. Prints ONE JSON line:
    {"metric": "span_overhead_pct", "value", "unit", "threshold_pct", "pass"}.

    The step is small but real — value_and_grad of an MSE through a
    (512,256)@(256,128) matmul plus an SGD update — so the denominator
    includes one genuine XLA dispatch per step, which is what a span wraps
    in practice.

    Methodology: the added work per traced step is exactly two span
    enter/exits (the outer per-step span plus one nested phase span, the
    shape ``Trainer.fit(trace_spans=True)`` emits), so that pair is timed
    in a tight loop where it is measurable to ~2% — and divided by the
    measured per-step time. A direct A/B difference of two ~1e2..1e3us
    step loops cannot resolve a sub-5% effect on a shared host (scheduler
    and frequency noise is itself +/-3-5% of the step at any size; in
    calibration it produced deltas from -4.6% to +9% for the same code),
    so the A/B delta is reported only as a diagnostic field.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    from sparkflow_tpu.obs import Tracer

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(512, 256).astype(np.float32))
    w = jnp.asarray(rs.rand(256, 128).astype(np.float32) * 0.1)
    y = jnp.asarray(rs.rand(512, 128).astype(np.float32))

    @jax.jit
    def step(w):
        def loss(w):
            return jnp.mean((x @ w - y) ** 2)
        l, g = jax.value_and_grad(loss)(w)
        return w - 1e-3 * g, l

    # warm up the compile so neither loop pays it
    w2, l = step(w)
    jax.block_until_ready((w2, l))

    tr = Tracer()

    # (1) cost of the added instrumentation, isolated: one nested span pair
    # per iteration, exactly the per-step shape the traced loop below adds.
    # Tight-loop minima are stable to ~2% where A/B step-loop deltas are not.
    pair_iters = 50000

    def pair_loop():
        t0 = time.perf_counter()
        with tr.activate():
            for i in range(pair_iters):
                with tr.span("bench/step", args={"i": i}):
                    with tr.span("bench/compute"):
                        pass
        return (time.perf_counter() - t0) / pair_iters

    span_pair_s = min(pair_loop() for _ in range(3))

    # (2) per-step time of the real jitted loop, plain vs traced,
    # interleaved (the traced number feeds the diagnostic A/B delta only)
    seg = 50

    def plain_seg():
        wi = w
        t0 = time.perf_counter()
        for _ in range(seg):
            wi, li = step(wi)
            jax.block_until_ready(li)
        return (time.perf_counter() - t0) / seg

    def traced_seg():
        wi = w
        t0 = time.perf_counter()
        with tr.activate():
            for i in range(seg):
                with tr.span("bench/step", args={"i": i}):
                    with tr.span("bench/compute"):
                        wi, li = step(wi)
                        jax.block_until_ready(li)
        return (time.perf_counter() - t0) / seg

    plain, traced = 1e9, 1e9
    for _ in range(10):
        plain = min(plain, plain_seg())
        traced = min(traced, traced_seg())

    overhead_pct = span_pair_s / plain * 100.0

    out = {
        "metric": "span_overhead_pct",
        "value": round(overhead_pct, 2),
        "unit": "%",
        "threshold_pct": 5.0,
        "pass": overhead_pct < 5.0,
        "spans_per_step": 2,
        "span_pair_us": round(span_pair_s * 1e6, 3),
        "plain_step_us": round(plain * 1e6, 2),
        "ab_delta_pct_diagnostic": round((traced - plain) / plain * 100.0, 2),
    }
    print(json.dumps(out))


def trace_overhead_main():
    """Micro-bench for distributed tracing: the full per-request tracing
    kit (traceparent parse, request+dispatch spans with trace args, tail
    retention verdict, flight-recorder begin/end — i.e. everything PR 20
    adds to a served request) costed against a real batched predict.
    Prints ONE JSON line:
    {"metric": "trace_overhead_ratio", "value", "unit", "threshold", "pass"}.

    ``value`` is the throughput ratio tracing-on / tracing-off, derived as
    ``t_request / (t_request + t_kit)``: the baseline is a real HTTP
    request through ``InferenceServer`` + ``ServingClient`` with the
    tracer disabled (the deployment configuration tracing competes with),
    and the kit cost is a tight-loop minimum — the same methodology as
    ``--span-overhead``, because a direct A/B of two HTTP loops cannot
    resolve a sub-2% effect on a shared host (its delta is reported as a
    diagnostic field only). The pin is >= 0.98x, i.e. tracing may cost at
    most 2% of per-request throughput.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile

    import sparkflow_tpu.nn as nn
    from sparkflow_tpu.graph_utils import build_graph
    from sparkflow_tpu.obs import FlightRecorder, TraceCollector, Tracer
    from sparkflow_tpu.obs.spans import TraceContext
    from sparkflow_tpu.serving import (InferenceEngine, InferenceServer,
                                       ServingClient)
    from sparkflow_tpu.utils.metrics import Metrics

    def mlp():
        x = nn.placeholder([None, 16], name="x")
        h = nn.dense(x, 32, activation="relu")
        out = nn.dense(h, 8, name="out")
        nn.mean_squared_error(x, out)

    rs = np.random.RandomState(0)
    weights = [rs.randn(16, 32).astype(np.float32),
               rs.randn(32).astype(np.float32),
               rs.randn(32, 8).astype(np.float32),
               rs.randn(8).astype(np.float32)]
    x = rs.rand(2, 16).astype(np.float32).tolist()

    def serve(tracer):
        eng = InferenceEngine(build_graph(mlp), weights, input_name="x:0",
                              output_name="out/BiasAdd:0", max_batch=16)
        srv = InferenceServer(eng, max_delay_ms=0.0, memory_watch=False,
                              tracer=tracer)
        srv.start()
        return srv, ServingClient(srv.url)

    def request_loop(client, reps=3, iters=40):
        for _ in range(10):
            client.predict_full(x)             # warm compile + connection
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                client.predict_full(x)
            dt = (time.perf_counter() - t0) / iters
            best = dt if best is None or dt < best else best
        return best

    # (1) baseline request cost over real HTTP, tracer disabled
    srv_off, c_off = serve(Tracer(enabled=False))
    t_request = request_loop(c_off)
    srv_off.stop()

    # (2) the tracing kit, isolated in a tight loop where it resolves to
    # ~2%: exactly what one traced request adds across router + replica
    metrics = Metrics()
    tr = Tracer()
    collector = TraceCollector(tr, metrics=metrics, head_sample=0.0)
    flight_path = os.path.join(tempfile.mkdtemp(prefix="trace-bench-"),
                               "replica-0.jsonl")
    flight = FlightRecorder(flight_path, tracer=tr, metrics=metrics)
    header = TraceContext.mint().to_header()
    kit_iters = 3000
    budget = 8   # decode ticks per request: a traced generate records one
    #              post-hoc span per tick, so the kit charges for them too

    def kit_loop():
        t0 = time.perf_counter()
        with tr.activate():
            for _ in range(kit_iters):
                ctx = TraceContext.parse(header)
                flight.begin(ctx.trace_id)
                with tr.span("router/request",
                             args={"request_id": "r",
                                   "trace_id": ctx.trace_id}):
                    with tr.span("router/dispatch",
                                 args={"trace_id": ctx.trace_id,
                                       "replica": "u", "hedge": False}):
                        tick = time.perf_counter()
                        for _ in range(budget):
                            tr.record("serving/decode_tick", tick,
                                      tick, args={"trace_id": ctx.trace_id})
                flight.end(ctx.trace_id)
                collector.should_keep(1.0)
        return (time.perf_counter() - t0) / kit_iters

    t_kit = min(kit_loop() for _ in range(3))
    flight.close()

    # (3) diagnostic A/B: the same HTTP loop with tracing fully on
    srv_on, c_on = serve(tr)
    t_request_on = request_loop(c_on)
    srv_on.stop()

    ratio = t_request / (t_request + t_kit)
    out = {
        "metric": "trace_overhead_ratio",
        "value": round(ratio, 4),
        "unit": "x (throughput, tracing-on / tracing-off)",
        "threshold": 0.98,
        "pass": ratio >= 0.98,
        "per_request_us": round(t_request * 1e6, 2),
        "trace_kit_us": round(t_kit * 1e6, 3),
        "ab_ratio_diagnostic": round(t_request / t_request_on, 4),
    }
    print(json.dumps(out))


def elastic_straggler_main():
    """Sync vs elastic DP under a deterministic 10x straggler. Prints ONE
    JSON line: {"metric": "elastic_dp_straggler_speedup", "value", ...}.

    Runs on the virtual-time engine (``parallel.elastic.run_virtual``):
    4 replicas with per-step costs [1, 1, 1, 10] simulated seconds train a
    small MLP for a fixed 60-virtual-second budget. The sync number is the
    ideal barrier bound on the same fleet (every step gated on the 10x
    replica, zero collective overhead — generous to sync), so the reported
    speedup is conservative and hardware-independent; the elastic number is
    what the fleet actually applied to the store inside the budget.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import optax

    import jax.numpy as jnp
    from sparkflow_tpu.parallel.elastic import (
        ElasticDPEngine, ReplicaSpec, sync_baseline_examples_per_sec)
    from sparkflow_tpu.utils.metrics import Metrics

    rs = np.random.RandomState(0)
    n, d, batch = 512, 16, 32
    X = rs.rand(n, d).astype(np.float32)
    W = rs.randn(d, 1).astype(np.float32)
    Y = X @ W + 0.01 * rs.randn(n, 1).astype(np.float32)

    def loss_fn(params, x, y, mask, rng):
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        pred = h @ params["w2"] + params["b2"]
        return jnp.mean((pred - y) ** 2)

    params0 = {"w1": jnp.zeros((d, 16)), "b1": jnp.zeros((16,)),
               "w2": jnp.zeros((16, 1)), "b2": jnp.zeros((1,))}
    costs = [1.0, 1.0, 1.0, 10.0]
    shards = [(X[i::4], Y[i::4]) for i in range(4)]

    t0 = time.perf_counter()
    eng = ElasticDPEngine(loss_fn, optax.adam(0.01), params0,
                          max_staleness=4, metrics=Metrics())
    res = eng.run_virtual(shards, [ReplicaSpec(cost_s=c) for c in costs],
                          epochs=10_000, batch_size=batch, seed=0,
                          deadline_s=60.0)
    host_s = time.perf_counter() - t0

    sync_eps = sync_baseline_examples_per_sec(costs, batch)
    speedup = res.examples_per_sec / sync_eps
    out = {
        "metric": "elastic_dp_straggler_speedup",
        "value": round(speedup, 2),
        "unit": "x vs ideal sync barrier",
        "threshold": 3.0,
        "pass": speedup >= 3.0,
        "elastic_examples_per_vsec": round(res.examples_per_sec, 1),
        "sync_examples_per_vsec": round(sync_eps, 1),
        "straggler_factor": 10,
        "replicas": len(costs),
        "virtual_budget_s": 60.0,
        "pushes_accepted": res.stats["accepted"],
        "pushes_rejected_stale": res.stats["rejected_stale"],
        "host_wall_s": round(host_s, 2),
    }
    print(json.dumps(out))


def decode_throughput_main():
    """Continuous vs static batching for autoregressive decode. Prints ONE
    JSON line: {"metric": "decode_continuous_vs_static_speedup", ...}.

    Same DecodeEngine (paged KV cache + AOT fixed-shape decode step) under
    both schedulers, same mixed-length workload. Static batching admits
    ``num_slots`` requests at a time and runs the group until its LONGEST
    member finishes — the convoy cost. Continuous batching retires each
    sequence at its own token budget and refills the slot immediately.
    Tokens/sec counts USEFUL tokens only; per-token latency percentiles
    come from the engine's per-step ``serving/decode/token_latency_ms``
    histogram during the continuous run.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from sparkflow_tpu.models.registry import (build_registry_spec,
                                               model_from_json)
    from sparkflow_tpu.serving.batcher import ContinuousBatcher
    from sparkflow_tpu.serving.decode import DecodeEngine
    from sparkflow_tpu.utils.metrics import Metrics

    spec = build_registry_spec("transformer_lm", vocab_size=97, hidden=64,
                               num_layers=2, num_heads=4, mlp_dim=128,
                               max_len=64, dropout=0.0)
    model = model_from_json(spec)
    params = model.init(jax.random.PRNGKey(0))
    num_slots = 8
    metrics = Metrics()
    eng = DecodeEngine(model, params, num_slots=num_slots, page_size=8,
                       seed=0, metrics=metrics)

    # mixed-length workload: mostly-short with a long tail — the shape
    # continuous batching exists for (a 24-token completion next to 3s)
    budgets = [3, 4, 3, 3, 3, 4, 3, 24] * 4
    rs = np.random.RandomState(0)
    prompts = [[int(t) for t in rs.randint(1, 97, size=rs.randint(2, 5))]
               for _ in budgets]
    useful = sum(budgets)

    def run_static():
        done_tokens = 0
        t0 = time.perf_counter()
        for g in range(0, len(budgets), num_slots):
            group = list(range(g, min(g + num_slots, len(budgets))))
            # static batching's other cost: every member reserves KV for
            # the group's LONGEST budget, since it stays resident (and
            # keeps being stepped) until the whole group finishes
            group_max = max(budgets[i] for i in group)
            slots = {}
            for i in group:
                info = eng.prefill(prompts[i], max_new_tokens=group_max,
                                   temperature=0.0)
                slots[info["slot"]] = [i, 1]  # request, tokens so far
            # the whole group steps until its longest member is done
            for _ in range(group_max - 1):
                out = eng.step()
                for slot, (i, n) in slots.items():
                    if slot in out and n < budgets[i]:
                        slots[slot][1] = min(budgets[i], n + len(out[slot]))
            for slot, (i, n) in slots.items():
                done_tokens += n
                eng.release(slot)
        return done_tokens, time.perf_counter() - t0

    def run_continuous():
        cb = ContinuousBatcher(eng, max_queue=len(budgets) + 1,
                               metrics=metrics)
        t0 = time.perf_counter()
        futs = [cb.submit(p, max_new_tokens=b, temperature=0.0)
                for p, b in zip(prompts, budgets)]
        done_tokens = sum(f.result(timeout=600)["num_tokens"] for f in futs)
        dt = time.perf_counter() - t0
        cb.close()
        return done_tokens, dt

    # warm both paths once (first step after prefill pays dispatch setup)
    info = eng.prefill(prompts[0][:2], max_new_tokens=2, temperature=0.0)
    eng.step()
    eng.release(info["slot"])

    static_tokens, static_s = run_static()
    cont_tokens, cont_s = run_continuous()
    assert static_tokens == cont_tokens == useful, \
        (static_tokens, cont_tokens, useful)

    static_tps = useful / static_s
    cont_tps = useful / cont_s
    speedup = cont_tps / static_tps
    pct = metrics.percentiles("serving/decode/token_latency_ms", (50, 99))
    p50, p99 = pct["p50"], pct["p99"]
    out = {
        "metric": "decode_continuous_vs_static_speedup",
        "value": round(speedup, 2),
        "unit": "x tokens/sec",
        "threshold": 2.0,
        "pass": speedup >= 2.0,
        "continuous_tokens_per_sec": round(cont_tps, 1),
        "static_tokens_per_sec": round(static_tps, 1),
        "token_latency_p50_ms": round(p50, 2),
        "token_latency_p99_ms": round(p99, 2),
        "requests": len(budgets),
        "useful_tokens": useful,
        "num_slots": num_slots,
        "steady_traces": eng.stats()["steady_traces"],
    }
    print(json.dumps(out))


def prefix_cache_main():
    """Shared-prefix KV caching + chunked prefill for the decode plane.
    Prints THREE JSON lines, one per pinned claim:

    - ``decode_prefix_hit_ttft_speedup`` — time-to-first-token on a
      prefix-hit prompt (shared system prefix already indexed) vs a cold
      prompt of the same length. The hit prefills only the un-shared
      suffix, so the ladder pass over the shared 40 tokens disappears.
    - ``decode_shared_prefix_throughput_gain`` — tokens/sec of a
      shared-system-prompt workload (16 requests, same 40-token prefix)
      through the ContinuousBatcher with sharing on vs off.
    - ``decode_chunked_prefill_intertoken_p95`` — inter-token p95 of
      in-flight short decodes while a 48-token prompt arrives mid-stream:
      unchunked (monolithic prefill stalls the decode loop) over chunked
      (prefill fused into the decode step, one chunk per step). >1 means
      chunking lowered the stall.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import functools

    import jax

    from sparkflow_tpu import ops
    from sparkflow_tpu.models.registry import (build_registry_spec,
                                               model_from_json)
    from sparkflow_tpu.serving import decode as decode_mod
    from sparkflow_tpu.serving.batcher import ContinuousBatcher
    from sparkflow_tpu.serving.decode import DecodeEngine

    # On CPU the pallas decode kernel runs in interpret mode (~100ms/step
    # for this model — pure emulation overhead that buries the prefill-side
    # effects this bench pins). The engine is handed the compiled jnp
    # reference instead: same math, cheap steps, the
    # TPU-like regime where prefill compute is the cost that matters. Both
    # arms of every comparison run the identical kernel, so ratios are fair.
    decode_mod.paged_attention = ops.paged_attention_reference

    # big enough that prefill compute dominates per-call dispatch overhead
    # on CPU — with a toy model every device call costs the same ~1.5ms and
    # no prefill optimization can show up in wall time
    spec = build_registry_spec("transformer_lm", vocab_size=97, hidden=256,
                               num_layers=4, num_heads=4, mlp_dim=1024,
                               max_len=128, dropout=0.0)
    model = model_from_json(spec)
    params = model.init(jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    sys_prefix = [int(t) for t in rs.randint(1, 97, size=96)]

    eng = DecodeEngine(model, params, num_slots=8, page_size=8, seed=0)

    # -- (a) TTFT: prefix hit vs cold ------------------------------------
    def ttft(prompt):
        t0 = time.perf_counter()
        info = eng.prefill(prompt, max_new_tokens=2, temperature=0.0)
        dt = time.perf_counter() - t0
        eng.release(info["slot"])
        return dt

    ttft(sys_prefix + [1, 2, 3, 4, 5, 6, 7, 8])  # seed index, warm dispatch
    repeats = 8
    hit_s = sorted(ttft(sys_prefix
                        + [int(t) for t in rs.randint(1, 97, size=8)])
                   for _ in range(repeats))[repeats // 2]
    cold_s = sorted(ttft([int(t) for t in rs.randint(1, 97, size=104)])
                    for _ in range(repeats))[repeats // 2]
    ttft_speedup = cold_s / hit_s
    hits_after_a = eng.kv.stats()["prefix_hits"]
    print(json.dumps({
        "metric": "decode_prefix_hit_ttft_speedup",
        "value": round(ttft_speedup, 2),
        "unit": "x cold/hit median TTFT",
        "threshold": 2.0,
        "pass": ttft_speedup >= 2.0,
        "ttft_hit_ms": round(hit_s * 1e3, 2),
        "ttft_cold_ms": round(cold_s * 1e3, 2),
        "prompt_len": 104,
        "shared_tokens": 96,
        "repeats": repeats,
        "prefix_hits": hits_after_a,
    }))

    # -- (b) shared-system-prompt workload throughput, sharing on vs off -
    tails = [[int(a), int(b)] for a, b in rs.randint(1, 97, size=(16, 2))]

    def workload_tps(engine):
        cb = ContinuousBatcher(engine, max_queue=32)
        try:
            t0 = time.perf_counter()
            futs = [cb.submit(sys_prefix + tail, max_new_tokens=8,
                              temperature=0.0) for tail in tails]
            toks = sum(f.result(timeout=600)["num_tokens"] for f in futs)
            return toks / (time.perf_counter() - t0)
        finally:
            cb.close()

    eng_off = DecodeEngine(model, params, num_slots=8, page_size=8, seed=0,
                           prefix_cache=False)
    workload_tps(eng_off)          # warm the off engine's dispatch path
    tps_off = workload_tps(eng_off)
    tps_on = workload_tps(eng)     # eng is warm from (a)
    tps_gain = tps_on / tps_off
    print(json.dumps({
        "metric": "decode_shared_prefix_throughput_gain",
        "value": round(tps_gain, 2),
        "unit": "x tokens/sec, sharing on/off",
        "threshold": 1.2,
        "pass": tps_gain >= 1.2,
        "tokens_per_sec_shared": round(tps_on, 1),
        "tokens_per_sec_unshared": round(tps_off, 1),
        "requests": len(tails),
        "tokens_saved": eng.kv.stats()["tokens_saved"],
        "steady_traces": eng.stats()["steady_traces"],
    }))

    # -- (c) inter-token p95 with a long prompt arriving mid-stream ------
    # a FRESH random long prompt per run: a reused one would be committed
    # to the prefix index by the first run, and the replay would prefill
    # only an 8-token suffix — erasing the very stall being measured
    def fresh_long():
        return [int(t) for t in rs.randint(1, 97, size=96)]

    def intertoken_gaps(engine, long_prompt):
        shorts = [engine.prefill([9 + i, 3 + i], max_new_tokens=12,
                                 temperature=0.0) for i in range(3)]
        last = {s["slot"]: time.perf_counter() for s in shorts}
        counts = {s["slot"]: 1 for s in shorts}
        gaps, long_slot = [], None
        for step_i in range(100):
            if step_i == 4:
                long_slot = engine.prefill(long_prompt, max_new_tokens=4,
                                           temperature=0.0)["slot"]
            out = engine.step()
            now = time.perf_counter()
            for s in list(counts):
                if s in out and counts[s] < 12:
                    gaps.append(now - last[s])
                    last[s] = now
                    counts[s] = min(12, counts[s] + len(out[s]))
                    if counts[s] == 12:
                        engine.release(s)
                        del counts[s], last[s]
            if not counts:
                break
        if long_slot is not None:
            engine.release(long_slot)
        return gaps

    eng_chunk = DecodeEngine(model, params, num_slots=8, page_size=8,
                             seed=0, prefill_chunk=8)
    intertoken_gaps(eng_chunk, fresh_long())   # warm both paths once
    intertoken_gaps(eng, fresh_long())
    p95 = lambda xs: float(np.percentile(np.asarray(xs) * 1e3, 95))
    p95_chunk = p95(intertoken_gaps(eng_chunk, fresh_long()))
    p95_mono = p95(intertoken_gaps(eng, fresh_long()))
    stall_ratio = p95_mono / p95_chunk
    print(json.dumps({
        "metric": "decode_chunked_prefill_intertoken_p95",
        "value": round(stall_ratio, 2),
        "unit": "x unchunked/chunked p95 gap",
        "threshold": 1.2,
        "pass": stall_ratio >= 1.2,
        "p95_unchunked_ms": round(p95_mono, 2),
        "p95_chunked_ms": round(p95_chunk, 2),
        "long_prompt_len": 96,
        "prefill_chunk": 8,
        "steady_traces_chunked": eng_chunk.stats()["steady_traces"],
    }))


def hot_swap_main():
    """Live weight hot-swap under sustained decode load: the same
    continuous-batching burst with and without a mid-burst publish + watcher
    swap. Prints ONE JSON line:
    {"metric": "decode_hot_swap_intertoken_p95", ...}.

    The swap arm runs a real WeightStore + WeightWatcher: one third of the
    way into the burst a new version is published; the watcher pulls,
    verifies, and hands it to the engine, which holds admissions until the
    active slots drain and then swaps at the token boundary. The pinned
    claims: zero client-visible failures, the serving version flips exactly
    ONCE, inter-token p95 stays within 1.3x the no-swap arm (in-flight
    sequences keep stepping through the drain — only admission waits), zero
    steady-state retraces (the AOT decode step is reused as-is), and the
    post-swap params are bitwise the published tree (greedy output equals a
    cold start on the new weights).
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile

    import jax

    from sparkflow_tpu.models.registry import (build_registry_spec,
                                               model_from_json)
    from sparkflow_tpu.serving.batcher import ContinuousBatcher
    from sparkflow_tpu.serving.decode import DecodeEngine
    from sparkflow_tpu.serving.weightstore import WeightStore, WeightWatcher
    from sparkflow_tpu.utils.metrics import Metrics

    spec = build_registry_spec("transformer_lm", vocab_size=97, hidden=64,
                               num_layers=2, num_heads=4, mlp_dim=128,
                               max_len=64, dropout=0.0)
    model = model_from_json(spec)
    p_old = model.init(jax.random.PRNGKey(0))
    p_new = model.init(jax.random.PRNGKey(1))

    budgets = [4, 3, 5, 3, 4, 3, 6, 3] * 6
    rs = np.random.RandomState(0)
    prompts = [[int(t) for t in rs.randint(1, 97, size=rs.randint(2, 5))]
               for _ in budgets]
    useful = sum(budgets)

    def run(with_swap):
        metrics = Metrics()
        eng = DecodeEngine(model, p_old, num_slots=8, page_size=8, seed=0,
                           metrics=metrics)
        info = eng.prefill(prompts[0][:2], max_new_tokens=2, temperature=0.0)
        eng.step()
        eng.release(info["slot"])  # warm: first step pays dispatch setup
        store = watcher = None
        if with_swap:
            store = WeightStore(tempfile.mkdtemp(prefix="hotswap_bench_"))
            watcher = WeightWatcher(store, [eng],
                                    poll_interval_s=0.005).start()
        cb = ContinuousBatcher(eng, max_queue=len(budgets) + 1,
                               metrics=metrics)
        failures = 0
        t0 = time.perf_counter()
        futs = [cb.submit(p, max_new_tokens=b, temperature=0.0)
                for p, b in zip(prompts, budgets)]
        if with_swap:
            while sum(f.done() for f in futs) < len(futs) // 3:
                time.sleep(0.002)
            store.publish(p_new)  # mid-burst: the watcher takes it from here
        tokens = 0
        for f in futs:
            try:
                tokens += f.result(timeout=600)["num_tokens"]
            except Exception:
                failures += 1
        dt = time.perf_counter() - t0
        cb.close()
        if with_swap:
            deadline = time.perf_counter() + 10.0
            while (eng.serving_version() != 1
                   and time.perf_counter() < deadline):
                eng.maybe_swap()  # drained after the burst: lands now
                time.sleep(0.01)
            watcher.stop()
        p95 = metrics.percentiles("serving/decode/token_latency_ms",
                                  (95,))["p95"]
        return eng, tokens, dt, p95, failures

    eng_base, tok_base, s_base, p95_base, fail_base = run(False)
    eng_swap, tok_swap, s_swap, p95_swap, fail_swap = run(True)

    assert tok_base == tok_swap == useful, (tok_base, tok_swap, useful)
    swap_stats = eng_swap.stats()
    # bitwise: the swapped engine IS a cold start on the published tree
    cold = DecodeEngine(model, p_new, num_slots=8, page_size=8, seed=0)
    leaves_a = jax.tree.leaves(eng_swap._params)
    leaves_b = jax.tree.leaves(cold._params)
    bitwise = len(leaves_a) == len(leaves_b) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(leaves_a, leaves_b))

    def greedy(e, prompt, n):
        info = e.prefill(list(prompt), max_new_tokens=n, temperature=0.0)
        toks = [info["token"]]
        while len(toks) < n:
            toks.extend(e.step().get(info["slot"], []))
        e.release(info["slot"])
        return toks

    parity = greedy(eng_swap, prompts[0], 6) == greedy(cold, prompts[0], 6)
    ratio = p95_swap / max(p95_base, 1e-9)
    out = {
        "metric": "decode_hot_swap_intertoken_p95",
        "value": round(ratio, 2),
        "unit": "x swap/no-swap p95",
        "threshold": 1.3,
        "pass": (ratio <= 1.3 and fail_base == fail_swap == 0
                 and swap_stats["swaps"] == 1 and bitwise and parity
                 and swap_stats["steady_traces"] == 0),
        "p95_no_swap_ms": round(p95_base, 2),
        "p95_swap_ms": round(p95_swap, 2),
        "tokens_per_sec_no_swap": round(tok_base / s_base, 1),
        "tokens_per_sec_swap": round(tok_swap / s_swap, 1),
        "client_failures": fail_base + fail_swap,
        "version_flips": swap_stats["swaps"],
        "serving_version": swap_stats["serving_version"],
        "bitwise_params_parity": bitwise,
        "greedy_parity": parity,
        "steady_traces": swap_stats["steady_traces"],
        "requests": len(budgets),
        "useful_tokens": useful,
    }
    print(json.dumps(out))


def spec_decode_main():
    """Speculative decoding on the paged decode plane: spec-on vs spec-off
    tokens/sec and inter-token p95. Prints ONE JSON line:
    {"metric": "decode_spec_speedup", ...}.

    Honest accounting: both arms monkeypatch the paged decode AND verify
    kernels to their compiled jnp references (same math, no pallas-interpreter emulation tax), so the ratio
    isolates what speculation actually changes: device dispatches per token.
    The draft is acceptance-favorable self-speculation with ``draft_layers
    == num_layers`` (the draft IS the target, so every greedy proposal is
    accepted) — the CPU-measurable win is dispatch amortization, k+1 tokens
    per draft+verify pair instead of one per step; the TPU win adds the
    FLOP gap between a real truncated draft and the full target. Greedy
    parity between the arms is asserted, not assumed.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import functools

    import jax

    from sparkflow_tpu import ops
    from sparkflow_tpu.models.registry import (build_registry_spec,
                                               model_from_json)
    from sparkflow_tpu.serving import decode as decode_mod
    from sparkflow_tpu.serving.decode import DecodeEngine
    from sparkflow_tpu.utils.metrics import Metrics

    decode_mod.paged_attention = ops.paged_attention_reference
    decode_mod.paged_attention_verify = (
        ops.paged_attention_verify_reference)

    # small model: per-call dispatch dominates compute, which is the regime
    # speculation's fewer-dispatches-per-token targets (on CPU; a TPU run
    # would also show the draft/target FLOP gap)
    spec = build_registry_spec("transformer_lm", vocab_size=97, hidden=32,
                               num_layers=2, num_heads=4, mlp_dim=64,
                               max_len=64, dropout=0.0)
    model = model_from_json(spec)
    params = model.init(jax.random.PRNGKey(0))
    num_slots, budget, spec_k = 8, 48, 11
    rs = np.random.RandomState(0)
    prompts = [[int(t) for t in rs.randint(1, 97, size=rs.randint(2, 6))]
               for _ in range(num_slots)]

    def run_arm(engine, budget):
        infos = [engine.prefill(p, max_new_tokens=budget, temperature=0.0)
                 for p in prompts]
        got = {i["slot"]: [i["token"]] for i in infos}
        live = set(got)
        t0 = time.perf_counter()
        while live:
            out = engine.step()
            for s in list(live):
                if s in out:
                    got[s].extend(out[s])
                    if len(got[s]) >= budget:
                        engine.release(s)
                        live.discard(s)
        dt = time.perf_counter() - t0
        order = [i["slot"] for i in infos]
        return [got[s][:budget] for s in order], dt

    def build(spec_on):
        m = Metrics()
        kw = dict(spec_k=spec_k, draft_layers=2) if spec_on else {}
        eng = DecodeEngine(model, params, num_slots=num_slots, page_size=8,
                           seed=0, metrics=m, **kw)
        run_arm(eng, 4)                 # warm the dispatch path
        return eng, m

    eng_off, m_off = build(False)
    eng_on, m_on = build(True)
    # interleaved paired reps: each rep times both arms back to back so
    # they share the machine's conditions of the moment, and the claimed
    # speedup is the MEDIAN of per-rep ratios — a single noisy rep (GC
    # pause, scheduler hiccup; the measured sections are only tens of ms)
    # can't flap the gate either way
    reps = 10
    ratios, dt_off_best, dt_on_best = [], None, None
    toks_off = toks_on = None
    for _ in range(reps):
        t_off, d_off = run_arm(eng_off, budget)
        t_on, d_on = run_arm(eng_on, budget)
        if toks_off is None:
            toks_off, toks_on = t_off, t_on
        assert t_off == toks_off and t_on == toks_on, \
            "greedy output unstable across reps"
        ratios.append(d_off / d_on)
        dt_off_best = d_off if dt_off_best is None else min(dt_off_best, d_off)
        dt_on_best = d_on if dt_on_best is None else min(dt_on_best, d_on)
    assert toks_on == toks_off, "speculative greedy output diverged"
    tps_off = num_slots * budget / dt_off_best
    tps_on = num_slots * budget / dt_on_best
    st_on = eng_on.stats()
    p95_off = m_off.percentiles("serving/decode/token_latency_ms",
                                (95,))["p95"]
    p95_on = m_on.percentiles("serving/decode/token_latency_ms",
                              (95,))["p95"]
    speedup = sorted(ratios)[len(ratios) // 2]
    p95_ratio = p95_off / p95_on
    print(json.dumps({
        "metric": "decode_spec_speedup",
        "value": round(speedup, 2),
        "unit": "x tokens/sec, spec on/off",
        "threshold": 1.5,
        "pass": bool(speedup >= 1.5 and p95_ratio > 1.0),
        "tokens_per_sec_spec": round(tps_on, 1),
        "tokens_per_sec_plain": round(tps_off, 1),
        "intertoken_p95_spec_ms": round(p95_on, 2),
        "intertoken_p95_plain_ms": round(p95_off, 2),
        "intertoken_p95_ratio": round(p95_ratio, 2),
        "spec_k": spec_k,
        "accept_rate": round(st_on["spec"]["accept_rate"], 3),
        "mean_accepted": round(st_on["spec"]["mean_accepted"], 2),
        "greedy_parity": True,
        "steady_traces_spec": st_on["steady_traces"],
    }))


def kv_quant_main():
    """Quantized KV cache: int8 pool vs bf16/f32 pool on the paged decode
    plane. Prints ONE JSON line: {"metric": "decode_kv_quant", ...}.

    Three claims, one run:

    - capacity: pages-per-byte from the engines' own ``stats()`` byte
      accounting — the int8 pool (rows + per-page-per-head scales) must fit
      >= 1.9x the pages into the same device bytes;
    - parity: tokens/sec int8 vs float on the same workload, MEDIAN of
      interleaved per-rep ratios, with greedy output asserted
      token-identical between the arms (quantization error ~1e-4 logits on
      this model, far under any argmax margin);
    - overload: byte-equalized pools (the int8 arm spends its byte budget
      on ~4x the pages) driven through the ContinuousBatcher at 2x the
      float arm's concurrent capacity — the admission-rejection rate read
      off ``batcher.stats()`` must DROP on the quantized arm.

    Honest accounting: both arms trace under ``force_xla_attention()`` so
    every AOT program runs the reference kernels (same
    math, no pallas-interpreter emulation tax on CPU); the ratio isolates
    what the pool layout changes — dequant arithmetic and page bytes.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from concurrent.futures import wait

    import jax

    from sparkflow_tpu.models.registry import (build_registry_spec,
                                               model_from_json)
    from sparkflow_tpu.ops.attention import force_xla_attention
    from sparkflow_tpu.serving import ContinuousBatcher, DecodeEngine, \
        QueueFull
    from sparkflow_tpu.utils.metrics import Metrics

    spec = build_registry_spec("transformer_lm", vocab_size=97, hidden=32,
                               num_layers=2, num_heads=4, mlp_dim=64,
                               max_len=64, dropout=0.0)
    model = model_from_json(spec)
    params = model.init(jax.random.PRNGKey(0))
    num_slots, budget = 8, 24
    rs = np.random.RandomState(0)
    prompts = [[int(t) for t in rs.randint(1, 97, size=rs.randint(2, 6))]
               for _ in range(num_slots)]

    def build(kv_quant, num_pages=None):
        with force_xla_attention():
            return DecodeEngine(model, params, num_slots=num_slots,
                                page_size=8, num_pages=num_pages, seed=0,
                                kv_quant=kv_quant, metrics=Metrics())

    def run_arm(engine, budget):
        infos = [engine.prefill(p, max_new_tokens=budget, temperature=0.0)
                 for p in prompts]
        got = {i["slot"]: [i["token"]] for i in infos}
        live = set(got)
        t0 = time.perf_counter()
        while live:
            out = engine.step()
            for s in list(live):
                if s in out:
                    got[s].extend(out[s])
                    if len(got[s]) >= budget:
                        engine.release(s)
                        live.discard(s)
        dt = time.perf_counter() - t0
        return [got[i["slot"]][:budget] for i in infos], dt

    eng_ref = build("bf16")
    eng_q = build("int8")
    run_arm(eng_ref, 4)                   # warm the dispatch paths
    run_arm(eng_q, 4)

    # -- capacity: pages per byte straight off the stats() accounting
    bpp_ref = eng_ref.stats()["kv"]["kv_bytes_per_page"]
    bpp_q = eng_q.stats()["kv"]["kv_bytes_per_page"]
    pages_per_byte_ratio = bpp_ref / bpp_q

    # -- parity: interleaved paired reps, median of per-rep ratios (one
    # noisy rep can't flap the gate), greedy text must not move at all
    reps = 7
    ratios, toks_ref, toks_q = [], None, None
    for _ in range(reps):
        t_ref, d_ref = run_arm(eng_ref, budget)
        t_q, d_q = run_arm(eng_q, budget)
        if toks_ref is None:
            toks_ref, toks_q = t_ref, t_q
        assert t_ref == toks_ref and t_q == toks_q, \
            "greedy output unstable across reps"
        ratios.append(d_ref / d_q)
    parity = toks_q == toks_ref
    tps_ratio = sorted(ratios)[len(ratios) // 2]

    # -- overload: same device byte budget, 2x the float arm's concurrent
    # capacity offered to both batchers. Each request needs 4 pages
    # (4-token prompt + 28 new = 32 tokens); the float pool holds 3
    # concurrent, the int8 pool turns the same bytes into enough pages
    # that all 8 slots admit.
    pages_ref = 13                            # 12 usable + scratch
    byte_budget = (pages_ref - 1) * bpp_ref
    pages_q = 1 + int(byte_budget // bpp_q)
    ov_ref = build("bf16", num_pages=pages_ref)
    ov_q = build("int8", num_pages=pages_q)
    prompt, new_toks = [5, 2, 8, 3], 28       # 32 tokens = 4 pages/request
    cap_ref = (pages_ref - 1) // 4
    target = 2 * cap_ref                      # 2x the float arm's capacity

    def overload(engine):
        """Closed loop: keep ``target`` generations outstanding for a fixed
        window, topping up the moment one completes; every top-up the
        batcher refuses at the door (queue of 1 already full because the
        pool can't admit) counts against this pool layout."""
        bat = ContinuousBatcher(engine, max_queue=1)
        futs = []
        try:
            deadline = time.perf_counter() + 2.0
            while time.perf_counter() < deadline:
                futs = [f for f in futs if not f.done()]
                while len(futs) < target:
                    try:
                        futs.append(bat.submit(prompt,
                                               max_new_tokens=new_toks))
                    except QueueFull:
                        break                 # counted by the batcher
                time.sleep(0.005)
            wait(futs, timeout=120)
            st = bat.stats()
        finally:
            bat.close()
        return st

    st_ref = overload(ov_ref)
    st_q = overload(ov_q)
    rej_ref = st_ref["rejection_rate"]
    rej_q = st_q["rejection_rate"]

    ok = bool(pages_per_byte_ratio >= 1.9 and parity
              and tps_ratio >= 0.7 and rej_q < rej_ref)
    print(json.dumps({
        "metric": "decode_kv_quant",
        "value": round(pages_per_byte_ratio, 2),
        "unit": "x pages per device byte, int8 vs float pool",
        "threshold": 1.9,
        "pass": ok,
        "bytes_per_page_float": bpp_ref,
        "bytes_per_page_int8": bpp_q,
        "tokens_per_sec_ratio_int8_vs_float": round(tps_ratio, 2),
        "greedy_parity": parity,
        "kv_quant_error": eng_q.stats()["kv_quant_error"],
        "overload_pages_float": pages_ref - 1,
        "overload_pages_int8": pages_q - 1,
        "overload_offered": st_ref["submitted"],
        "overload_capacity_float": cap_ref,
        "rejection_rate_float": round(rej_ref, 3),
        "rejection_rate_int8": round(rej_q, 3),
        "steady_traces_int8": eng_q.stats()["steady_traces"],
        "platform": "cpu",
    }))


def tp_decode_main():
    """Tensor-parallel decode: tp=2 over a 2-virtual-device CPU mesh vs the
    same engine unsharded. Prints ONE JSON line:
    {"metric": "decode_tp_shard", ...}.

    What a CPU host can honestly measure about TP is **placement and
    parity**, not speed — two host-backed virtual devices share the same
    cores, so the gate is (a) greedy token parity tp=2 vs tp=1 through the
    REAL interpret-mode pallas kernels (each shard running the unmodified
    kernel over its heads slice), and (b) the structural claim: at-rest
    KV+param bytes per device at ~1/tp of the replicated baseline, read
    from ``stats()['parallel']``. Throughput/p95 for both arms are measured
    anyway — interleaved paired reps, median of per-rep ratios, exactly the
    spec-decode protocol — and reported informationally (expect ~1x or
    worse on CPU; the TPU win is the halved per-device weight/KV residency
    and the matmul split across chips).
    """
    _zero_bench_env(2)
    import functools

    import jax

    from sparkflow_tpu import ops
    from sparkflow_tpu.models.registry import (build_registry_spec,
                                               model_from_json)
    from sparkflow_tpu.parallel.mesh import make_mesh
    from sparkflow_tpu.serving import decode as decode_mod
    from sparkflow_tpu.serving.decode import DecodeEngine
    from sparkflow_tpu.sharding import ShardingConfig
    from sparkflow_tpu.utils.metrics import Metrics

    spec = build_registry_spec("transformer_lm", vocab_size=97, hidden=64,
                               num_layers=2, num_heads=4, mlp_dim=128,
                               max_len=64, dropout=0.0)
    model = model_from_json(spec)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh({"tp": 2})
    cfg = ShardingConfig(tp_axis="tp")
    num_slots, budget = 8, 32
    rs = np.random.RandomState(0)
    prompts = [[int(t) for t in rs.randint(1, 97, size=rs.randint(2, 6))]
               for _ in range(num_slots)]

    def run_arm(engine, budget):
        infos = [engine.prefill(p, max_new_tokens=budget, temperature=0.0)
                 for p in prompts]
        got = {i["slot"]: [i["token"]] for i in infos}
        live = set(got)
        t0 = time.perf_counter()
        while live:
            out = engine.step()
            for s in list(live):
                if s in out:
                    got[s].extend(out[s])
                    if len(got[s]) >= budget:
                        engine.release(s)
                        live.discard(s)
        dt = time.perf_counter() - t0
        order = [i["slot"] for i in infos]
        return [got[s][:budget] for s in order], dt

    # parity arm: the real pallas kernels (interpret mode on CPU), shards
    # feeding the unmodified kernel their local heads slice
    par1 = DecodeEngine(model, params, num_slots=num_slots, page_size=8,
                        seed=0)
    par2 = DecodeEngine(model, params, num_slots=num_slots, page_size=8,
                        seed=0, mesh=mesh, sharding=cfg)
    pt1, _ = run_arm(par1, 8)
    pt2, _ = run_arm(par2, 8)
    kernel_parity = pt1 == pt2
    assert kernel_parity, "tp=2 diverged from tp=1 under the pallas kernels"

    # timing arms: compiled jnp reference kernels, so the ratio reflects orchestration, not interpreter tax
    decode_mod.paged_attention = ops.paged_attention_reference
    decode_mod.paged_attention_verify = (
        ops.paged_attention_verify_reference)
    m1, m2 = Metrics(), Metrics()
    eng1 = DecodeEngine(model, params, num_slots=num_slots, page_size=8,
                        seed=0, metrics=m1)
    eng2 = DecodeEngine(model, params, num_slots=num_slots, page_size=8,
                        seed=0, metrics=m2, mesh=mesh, sharding=cfg)
    run_arm(eng1, 4)  # warm the dispatch paths
    run_arm(eng2, 4)
    reps = 10
    ratios, toks1, toks2 = [], None, None
    dt1_best = dt2_best = None
    for _ in range(reps):
        t1, d1 = run_arm(eng1, budget)
        t2, d2 = run_arm(eng2, budget)
        if toks1 is None:
            toks1, toks2 = t1, t2
        assert t1 == toks1 and t2 == toks2, \
            "greedy output unstable across reps"
        ratios.append(d1 / d2)
        dt1_best = d1 if dt1_best is None else min(dt1_best, d1)
        dt2_best = d2 if dt2_best is None else min(dt2_best, d2)
    assert toks1 == toks2, "tp=2 greedy output diverged from tp=1"
    s1, s2 = eng1.stats(), eng2.stats()
    b1 = (s1["parallel"]["kv_bytes_per_device"]
          + s1["parallel"]["param_bytes_per_device"])
    b2 = (s2["parallel"]["kv_bytes_per_device"]
          + s2["parallel"]["param_bytes_per_device"])
    mem_ratio = b2 / b1
    speed = sorted(ratios)[len(ratios) // 2]
    p95_1 = m1.percentiles("serving/decode/token_latency_ms", (95,))["p95"]
    p95_2 = m2.percentiles("serving/decode/token_latency_ms", (95,))["p95"]
    ok = kernel_parity and mem_ratio <= 0.65 \
        and s2["steady_traces"] == 0
    print(json.dumps({
        "metric": "decode_tp_shard",
        "value": round(mem_ratio, 3),
        "unit": "per-device KV+param bytes, tp=2 / tp=1",
        "threshold": 0.65,
        "pass": bool(ok),
        "kv_bytes_per_device_tp1": s1["parallel"]["kv_bytes_per_device"],
        "kv_bytes_per_device_tp2": s2["parallel"]["kv_bytes_per_device"],
        "param_bytes_per_device_tp1": s1["parallel"]["param_bytes_per_device"],
        "param_bytes_per_device_tp2": s2["parallel"]["param_bytes_per_device"],
        "tp_speed_ratio_median": round(speed, 2),
        "tokens_per_sec_tp1": round(num_slots * budget / dt1_best, 1),
        "tokens_per_sec_tp2": round(num_slots * budget / dt2_best, 1),
        "intertoken_p95_tp1_ms": round(p95_1, 2),
        "intertoken_p95_tp2_ms": round(p95_2, 2),
        "greedy_parity": True,
        "kernel_parity": bool(kernel_parity),
        "steady_traces_tp2": s2["steady_traces"],
        "tp": 2,
        "platform": "cpu-hostdevices",
    }))


def pp_decode_main():
    """Pipeline-parallel decode: pp=2 over a 2-virtual-device CPU mesh.
    Prints ONE JSON line: {"metric": "decode_pp_wave", ...}.

    Two claims, two gates. (a) Structural: at-rest KV+param bytes per
    device at ~1/pp of the replicated baseline (the pool shards on its
    layers axis, the params stage-stack), plus greedy token parity pp=2
    vs pp=1 through the REAL interpret-mode pallas kernels under BOTH
    schedules. (b) Scheduling: micro-token wave scheduling vs the
    single-wave pp schedule at equal batch, tokens/sec median-of-ratios
    >= 1.5x. Unlike the tp bench this speed gate is honest on CPU: the
    single-wave schedule burns pp passes of every-stage compute per
    token (1/pp efficiency by construction), while waves keep every
    stage usefully busy on a different wave's token — the ratio measures
    bubble amortization, not device count. Timing arms run the
    compiled jnp reference kernels
    on a compute-bound model so orchestration, not interpreter tax,
    sets the clock; interleaved paired reps, spec-decode protocol.
    """
    _zero_bench_env(2)
    import functools

    import jax

    from sparkflow_tpu import ops
    from sparkflow_tpu.models.registry import (build_registry_spec,
                                               model_from_json)
    from sparkflow_tpu.parallel.mesh import make_mesh
    from sparkflow_tpu.serving import decode as decode_mod
    from sparkflow_tpu.serving.decode import DecodeEngine
    from sparkflow_tpu.sharding import ShardingConfig
    from sparkflow_tpu.utils.metrics import Metrics

    mesh = make_mesh({"pp": 2})
    cfg = ShardingConfig(pp_axis="pp")
    num_slots, budget = 16, 16
    rs = np.random.RandomState(0)

    def run_arm(engine, prompts, budget):
        infos = [engine.prefill(p, max_new_tokens=budget, temperature=0.0)
                 for p in prompts]
        got = {i["slot"]: [i["token"]] for i in infos}
        live = set(got)
        t0 = time.perf_counter()
        while live:
            out = engine.step()
            for s in list(live):
                if s in out:
                    got[s].extend(out[s])
                    if len(got[s]) >= budget:
                        engine.release(s)
                        live.discard(s)
        dt = time.perf_counter() - t0
        order = [i["slot"] for i in infos]
        return [got[s][:budget] for s in order], dt

    # parity arm: small model, the real pallas kernels (interpret mode on
    # CPU), both staged schedules against the unsharded engine
    pspec = build_registry_spec("transformer_lm", vocab_size=97, hidden=64,
                                num_layers=2, num_heads=4, mlp_dim=128,
                                max_len=64, dropout=0.0)
    pmodel = model_from_json(pspec)
    pparams = pmodel.init(jax.random.PRNGKey(0))
    pprompts = [[int(t) for t in rs.randint(1, 97, size=rs.randint(2, 6))]
                for _ in range(num_slots)]
    par1 = DecodeEngine(pmodel, pparams, num_slots=num_slots, page_size=8,
                        seed=0)
    parw = DecodeEngine(pmodel, pparams, num_slots=num_slots, page_size=8,
                        seed=0, mesh=mesh, sharding=cfg)
    pars = DecodeEngine(pmodel, pparams, num_slots=num_slots, page_size=8,
                        seed=0, mesh=mesh, sharding=cfg, pp_wave=False)
    pt1, _ = run_arm(par1, pprompts, 8)
    ptw, _ = run_arm(parw, pprompts, 8)
    pts, _ = run_arm(pars, pprompts, 8)
    kernel_parity = pt1 == ptw == pts
    assert kernel_parity, "pp=2 diverged from pp=1 under the pallas kernels"
    s1, sw = par1.stats(), parw.stats()
    b1 = (s1["parallel"]["kv_bytes_per_device"]
          + s1["parallel"]["param_bytes_per_device"])
    b2 = (sw["parallel"]["kv_bytes_per_device"]
          + sw["parallel"]["param_bytes_per_device"])
    mem_ratio = b2 / b1

    # timing arms: compute-bound model (blocks dominate the per-token
    # FLOPs; the head is schedule-neutral), reference kernels, BOTH arms
    # pp=2 — only the schedule differs
    tspec = build_registry_spec("transformer_lm", vocab_size=512,
                                hidden=1024, num_layers=4, num_heads=16,
                                mlp_dim=4096, max_len=64, dropout=0.0)
    tmodel = model_from_json(tspec)
    tparams = tmodel.init(jax.random.PRNGKey(0))
    tprompts = [[int(t) for t in rs.randint(1, 512, size=rs.randint(2, 6))]
                for _ in range(num_slots)]
    decode_mod.paged_attention = ops.paged_attention_reference
    decode_mod.paged_attention_verify = (
        ops.paged_attention_verify_reference)
    mw, ms = Metrics(), Metrics()
    eng_wave = DecodeEngine(tmodel, tparams, num_slots=num_slots,
                            page_size=8, seed=0, metrics=mw, mesh=mesh,
                            sharding=cfg)
    eng_sw = DecodeEngine(tmodel, tparams, num_slots=num_slots, page_size=8,
                          seed=0, metrics=ms, mesh=mesh, sharding=cfg,
                          pp_wave=False)
    run_arm(eng_wave, tprompts, 4)  # warm the dispatch paths
    run_arm(eng_sw, tprompts, 4)
    reps = 10
    ratios, toks_w, toks_s = [], None, None
    dtw_best = dts_best = None
    for _ in range(reps):
        ts, ds = run_arm(eng_sw, tprompts, budget)
        tw, dw = run_arm(eng_wave, tprompts, budget)
        if toks_w is None:
            toks_w, toks_s = tw, ts
        assert tw == toks_w and ts == toks_s, \
            "greedy output unstable across reps"
        ratios.append(ds / dw)
        dtw_best = dw if dtw_best is None else min(dtw_best, dw)
        dts_best = ds if dts_best is None else min(dts_best, ds)
    assert toks_w == toks_s, "wave scheduling diverged from single-wave"
    stw, sts = eng_wave.stats(), eng_sw.stats()
    speed = sorted(ratios)[len(ratios) // 2]
    p95_w = mw.percentiles("serving/decode/token_latency_ms", (95,))["p95"]
    p95_s = ms.percentiles("serving/decode/token_latency_ms", (95,))["p95"]
    ok = kernel_parity and mem_ratio <= 0.65 and speed >= 1.5 \
        and stw["steady_traces"] == 0 and sts["steady_traces"] == 0
    print(json.dumps({
        "metric": "decode_pp_wave",
        "value": round(speed, 2),
        "unit": "tokens/sec, wave / single-wave (both pp=2, equal batch)",
        "threshold": 1.5,
        "pass": bool(ok),
        "mem_ratio": round(mem_ratio, 3),
        "mem_threshold": 0.65,
        "kv_bytes_per_device_pp1": s1["parallel"]["kv_bytes_per_device"],
        "kv_bytes_per_device_pp2": sw["parallel"]["kv_bytes_per_device"],
        "param_bytes_per_device_pp1": s1["parallel"]["param_bytes_per_device"],
        "param_bytes_per_device_pp2": sw["parallel"]["param_bytes_per_device"],
        "tokens_per_sec_wave": round(num_slots * budget / dtw_best, 1),
        "tokens_per_sec_single_wave": round(num_slots * budget / dts_best, 1),
        "intertoken_p95_wave_ms": round(p95_w, 2),
        "intertoken_p95_single_wave_ms": round(p95_s, 2),
        "wave_ticks": stw["parallel"]["wave_ticks"],
        "greedy_parity": True,
        "kernel_parity": bool(kernel_parity),
        "steady_traces_wave": stw["steady_traces"],
        "steady_traces_single_wave": sts["steady_traces"],
        "pp": 2,
        "platform": "cpu-hostdevices",
    }))


def _zero_bench_env(n_dev: int = 8):
    """8 virtual CPU devices for the zero-stage benches: set BEFORE the
    first jax import (flags are read at backend init). Deterministic and
    hardware-independent — the memory numbers are structural (eval_shape
    byte accounting) and the step-time ratio compares two programs on the
    SAME backend."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_dev}")


def _zero_step_setup(stage: int, n_dev: int):
    """Build the jitted unified dp step for one zero stage plus its initial
    (params, opt_state), on an mlp big enough that step time is compute-
    not dispatch-bound on CPU."""
    import jax
    import jax.numpy as jnp
    from sparkflow_tpu.models import model_from_json
    from sparkflow_tpu.models.presets import mlp
    from sparkflow_tpu.optimizers import build_optimizer
    from sparkflow_tpu.optimizers_sharded import (
        place_zero1_state, shard_zero3_params, sharded_update,
        zero3_param_shardings)
    from sparkflow_tpu.parallel.dp import make_dp_train_step
    from sparkflow_tpu.parallel.mesh import make_mesh
    from sparkflow_tpu.sharding import ShardingConfig

    d_in, n_cls = 128, 10
    model = model_from_json(mlp(d_in, n_cls, hidden=(512, 512)))
    opt = build_optimizer("adam", 1e-3, None)
    mesh = make_mesh({"dp": n_dev})
    cfg = ShardingConfig(zero_stage=stage)
    step = make_dp_train_step(model, opt, mesh, "x:0", "y:0", sharding=cfg)
    p0 = model.init(jax.random.PRNGKey(0))
    if stage == 0:
        params, state = p0, opt.init(p0)
    else:
        state = place_zero1_state(
            sharded_update(opt, n_dev, "dp").init(p0), mesh, n_dev)
        if stage >= 3:
            params = shard_zero3_params(p0, n_dev)
            params = jax.tree.map(
                jax.device_put, params,
                zero3_param_shardings(params, mesh, n_dev))
        else:
            params = jax.tree.map(jnp.array, p0)
    return model, opt, mesh, step, params, state, p0


def _time_zero_step(step, params, state, n_dev, *, warmup=3, reps=20):
    """Median wall time of one compiled step (seconds)."""
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    batch = 8 * n_dev
    x = jnp.asarray(rs.randn(batch, 128), jnp.float32)
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rs.randint(0, 10, batch)])
    mask = jnp.ones((batch,), jnp.float32)
    rng = jax.random.PRNGKey(1)
    times = []
    for i in range(warmup + reps):
        r = jax.random.fold_in(rng, i)
        t0 = time.perf_counter()
        params, state, loss = step(params, state, x, y, mask, r)
        jax.block_until_ready(loss)
        if i >= warmup:
            times.append(time.perf_counter() - t0)
    return float(np.median(times))


def dp_zero2_main():
    """ZeRO-2 vs ZeRO-1: same model, same mesh, both axes of the win.
    Prints ONE JSON line: {"metric": "dp_zero2_vs_zero1", ...}.

    - memory: grad+opt bytes live at update time (structural eval_shape
      accounting, ``optimizers_sharded.zero_memory_report``) vs the ideal
      1/dp floor — stage 2 must land within 1.3x of ideal (padding and the
      gathered-params buffer are the honest overhead).
    - time: median compiled step time, stage 2 / stage 1 — must stay
      within 1.10x (the all-gather moves updated params instead of
      updates; same bytes on the wire, so parity is the expectation).
    """
    _zero_bench_env(8)
    from sparkflow_tpu.optimizers_sharded import zero_memory_report

    n_dev = 8
    model, opt, mesh, step1, p1, s1, p0 = _zero_step_setup(1, n_dev)
    _, _, _, step2, p2, s2, _ = _zero_step_setup(2, n_dev)
    t1 = _time_zero_step(step1, p1, s1, n_dev)
    t2 = _time_zero_step(step2, p2, s2, n_dev)
    time_ratio = t2 / t1

    rep = zero_memory_report(opt, p0, n_dev, 2)
    bytes_ratio = rep["grad_opt_at_update"] / rep["ideal_grad_opt"]
    ok = bytes_ratio <= 1.3 and time_ratio <= 1.10
    out = {
        "metric": "dp_zero2_vs_zero1",
        "value": round(time_ratio, 3),
        "unit": "x step time vs zero1",
        "threshold": 1.10,
        "pass": bool(ok),
        "grad_opt_bytes_ratio_vs_ideal": round(bytes_ratio, 3),
        "bytes_threshold": 1.3,
        "grad_opt_at_update_bytes": rep["grad_opt_at_update"],
        "ideal_grad_opt_bytes": rep["ideal_grad_opt"],
        "zero1_step_ms": round(t1 * 1e3, 2),
        "zero2_step_ms": round(t2 * 1e3, 2),
        "dp": n_dev,
        "platform": "cpu-hostdevices",
    }
    print(json.dumps(out))


def dp_zero3_main():
    """ZeRO-3 at-rest memory: params + opt state per device vs replicated.
    Prints ONE JSON line: {"metric": "dp_zero3_memory", ...}.

    The value is the at-rest fraction (sharded bytes / replicated bytes);
    ideal is 1/dp, the threshold allows 1.3x of that for flat-layout
    padding. Step time vs zero1 is reported informationally — stage 3
    trades one all-gather per step for the 1/dp param residency.
    """
    _zero_bench_env(8)
    from sparkflow_tpu.optimizers_sharded import zero_memory_report

    n_dev = 8
    model, opt, mesh, step1, p1, s1, p0 = _zero_step_setup(1, n_dev)
    _, _, _, step3, p3, s3, _ = _zero_step_setup(3, n_dev)
    t1 = _time_zero_step(step1, p1, s1, n_dev)
    t3 = _time_zero_step(step3, p3, s3, n_dev)

    rep = zero_memory_report(opt, p0, n_dev, 3)
    at_rest = rep["params_at_rest"] + rep["opt_state_at_rest"]
    full = rep["full_params"] + rep["full_opt_state"]
    frac = at_rest / full
    threshold = 1.3 / n_dev
    out = {
        "metric": "dp_zero3_memory",
        "value": round(frac, 4),
        "unit": "at-rest bytes fraction vs replicated",
        "threshold": round(threshold, 4),
        "pass": bool(frac <= threshold),
        "params_at_rest_bytes": rep["params_at_rest"],
        "opt_state_at_rest_bytes": rep["opt_state_at_rest"],
        "full_params_bytes": rep["full_params"],
        "full_opt_state_bytes": rep["full_opt_state"],
        "zero1_step_ms": round(t1 * 1e3, 2),
        "zero3_step_ms": round(t3 * 1e3, 2),
        "zero3_vs_zero1_step_time": round(t3 / t1, 3),
        "dp": n_dev,
        "platform": "cpu-hostdevices",
    }
    print(json.dumps(out))


def sim_main():
    """Fleet-simulator bench: scale wall-clock pin, the legacy-vs-debit
    generate pick rule A/B in sim, and the REAL-fleet confirmation of the
    sim-found improvement. Prints ONE JSON line:
    {"metric": "sim_fleet_whatif", ...}.

    Three parts:

    1. **scale** — 1000 replicas x 1,000,000 requests through the full
       event loop (real policies, real breakers on the virtual clock);
       the wall-clock is the pinned claim ("fleet what-ifs are cheap").
    2. **sim A/B** — the heterogeneous-pool what-if that motivated the
       inflight-debited byte-headroom generate rule: legacy vs debit on
       the same trace, p95 ratio reported.
    3. **real confirm** — two real DecodeEngine replicas (one big KV
       pool, one small) behind a real RouterServer; concurrent generate
       bursts under each pick rule (module-swapped policy, everything
       else identical). The debit rule must not lose: the sim's
       prediction is only landed because this confirms it.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import threading

    import jax

    from sparkflow_tpu.models.registry import (build_registry_spec,
                                               model_from_json)
    from sparkflow_tpu.serving import (ContinuousBatcher, DecodeEngine,
                                       InferenceServer, RouterServer,
                                       ServingClient, policies)
    from sparkflow_tpu.sim import (CostModel, FleetSimulator, ReplicaSpec,
                                   legacy_generate_pick_key,
                                   synthetic_trace)
    from sparkflow_tpu.sim.calibrate import StubEngine

    cost = CostModel.from_bench_notes()
    # -- part 1: scale pin ---------------------------------------------------
    wall_bound_s = 120.0
    tr = synthetic_trace(1_000_000, seed=7, rate_rps=40000.0,
                         prompt_range=(16, 1024), output_range=(8, 256))
    specs = [ReplicaSpec(slots=8, pages_total=4096) for _ in range(1000)]
    scale = FleetSimulator(specs, tr, cost, mode="generate", seed=0).run()
    scale_ok = (scale.completed + scale.rejected == 1_000_000
                and scale.wall_s <= wall_bound_s)

    # -- part 2: the sim A/B that found the rule -----------------------------
    specs = ([ReplicaSpec(slots=16, pages_total=8192,
                          kv_bytes_per_page=4 << 20) for _ in range(2)] +
             [ReplicaSpec(slots=16, pages_total=1024,
                          kv_bytes_per_page=1 << 20) for _ in range(6)])
    tr = synthetic_trace(20000, seed=3, rate_rps=900.0)
    legacy = FleetSimulator(specs, tr, cost, mode="generate", seed=0,
                            pick_key=legacy_generate_pick_key).run()
    debit = FleetSimulator(specs, tr, cost, mode="generate", seed=0).run()
    sim_ratio = legacy.latency_p95_ms / max(debit.latency_p95_ms, 1e-9)

    # -- part 3: real mixed-pool fleet confirm -------------------------------
    spec = build_registry_spec("transformer_lm", vocab_size=61, hidden=64,
                               num_layers=4, num_heads=4, mlp_dim=256,
                               max_len=64, dropout=0.0)
    model = model_from_json(spec)
    params = model.init(jax.random.PRNGKey(0))

    def burst_p95(pick_key_fn):
        engines = [DecodeEngine(model, params, num_slots=4, page_size=8,
                                num_pages=pages, seed=0)
                   for pages in (64, 9)]    # big pool vs tight pool
        cbs = [ContinuousBatcher(e, max_queue=32) for e in engines]
        servers = [InferenceServer(StubEngine(0.0), generate_batcher=cb,
                                   max_delay_ms=1.0).start() for cb in cbs]
        router = RouterServer([s.url for s in servers],
                              probe_interval_s=0.05,
                              dispatch_retries=3).start()
        orig = policies.generate_pick_key
        policies.generate_pick_key = pick_key_fn
        lats, errs = [], [0]
        try:
            m = router.membership
            deadline = time.time() + 10.0
            while time.time() < deadline:
                if all(r.decode_pages_free > 0 for r in m.replicas):
                    break
                time.sleep(0.02)
            cli = ServingClient(router.url, timeout=60, retries=2)
            cli.generate([3, 1, 4], max_new_tokens=4)  # unmeasured warm-up
            lock = threading.Lock()

            # 3 prompt + 56 new tokens = 59 -> 8 pages @ page_size 8:
            # the tight pool (9 pages) holds ONE concurrent stream, the
            # big pool (64) is slot-limited at 4. A 10-wide burst is
            # where the rules diverge: legacy alternates on inflight
            # (near-even split -> the tight pool serializes its share
            # one generation at a time), the debit rule stops feeding
            # it once the debited headroom predicts exhaustion.
            def one(i):
                t0 = time.perf_counter()
                try:
                    cli.generate([1 + i % 50, 2, 3], max_new_tokens=56)
                    ok = True
                except Exception:  # noqa: BLE001 - counted
                    ok = False
                dt = (time.perf_counter() - t0) * 1e3
                with lock:
                    if ok:
                        lats.append(dt)
                    else:
                        errs[0] += 1

            for wave in range(4):            # 4 bursts of 10 concurrent
                ths = [threading.Thread(target=one, args=(wave * 10 + i,))
                       for i in range(10)]
                for t in ths:
                    t.start()
                for t in ths:
                    t.join(timeout=60.0)
            cli.close()
        finally:
            policies.generate_pick_key = orig
            router.stop()
            for cb in cbs:
                cb.close()
            for s in servers:
                s.stop()
        lats.sort()
        p95 = lats[min(len(lats) - 1, int(round(0.95 * (len(lats) - 1))))] \
            if lats else float("inf")
        return p95, len(lats), errs[0]

    # debit arm: est matched to the workload (8 pages/stream), the
    # documented deployment knob — EST_PAGES_PER_STREAM defaults to the
    # production workload median, this harness decodes 59-token streams
    new_rule = policies.generate_pick_key
    debit_est8 = lambda v: new_rule(v, est_pages_per_stream=8)  # noqa: E731
    real_legacy_p95, n_legacy, e_legacy = burst_p95(legacy_generate_pick_key)
    real_debit_p95, n_debit, e_debit = burst_p95(debit_est8)
    real_ratio = real_legacy_p95 / max(real_debit_p95, 1e-9)
    # the confirmation: the sim-found rule must not lose on real hardware
    # (the structural effect measures ~1.2x; 1.05 absorbs burst noise)
    confirmed = (e_debit == 0 and n_debit == 40
                 and real_debit_p95 <= real_legacy_p95 * 1.05)

    out = {
        "metric": "sim_fleet_whatif",
        "scale_replicas": 1000,
        "scale_requests": 1_000_000,
        "scale_wall_s": round(scale.wall_s, 2),
        "scale_wall_bound_s": wall_bound_s,
        "scale_sim_time_s": round(scale.sim_time_s, 2),
        "scale_throughput_sim_rps": round(scale.completed
                                          / max(scale.wall_s, 1e-9)),
        "scale_digest": scale.digest[:16],
        "pass": bool(scale_ok),
        "sim_ab_legacy_p95_ms": round(legacy.latency_p95_ms, 1),
        "sim_ab_debit_p95_ms": round(debit.latency_p95_ms, 1),
        "sim_ab_p95_speedup": round(sim_ratio, 2),
        "sim_ab_legacy_queue_full": legacy.queue_full,
        "sim_ab_debit_queue_full": debit.queue_full,
        "real_legacy_p95_ms": round(real_legacy_p95, 1),
        "real_debit_p95_ms": round(real_debit_p95, 1),
        "real_p95_speedup": round(real_ratio, 2),
        "real_errors": e_legacy + e_debit,
        "real_confirmed": bool(confirmed),
        "platform": "cpu",
    }
    print(json.dumps(out))


def cold_start_main():
    """Zero-compile cold start bench: boot-to-first-token with vs without
    the serialized-executable store. Prints ONE JSON line:
    {"metric": "cold_start_boot", ...}.

    Three boots of the same engines (a predict MLP bucket ladder and a
    transformer DecodeEngine), same process, same machine:

    1. **populate** — boot with an empty ``ExecutableStore`` directory:
       full compiles, store saves every executable (untimed);
    2. **compile boot** — boot with NO store: every executable pays
       tracing + lowering + XLA (the status quo a spawned replica paid
       before this store existed);
    3. **serialized boot** — boot against the populated store: every
       executable deserializes (``coldstart/hits``), zero compiles.

    Boot time = constructor (which warms up the full AOT ladder) + the
    first real result (a predict / a prefill + one decode step). The
    pinned claim for BENCH_NOTES.md is the compile/serialized ratio; the
    elastic-fleet value is that this latency sits between "autoscaler
    ordered capacity" and "capacity takes traffic".
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import shutil
    import tempfile

    import jax
    import numpy as np

    from sparkflow_tpu.models.registry import (build_registry_spec,
                                               model_from_json)
    from sparkflow_tpu.serving import DecodeEngine, InferenceEngine
    from sparkflow_tpu.utils.metrics import Metrics

    spec = build_registry_spec("transformer_lm", vocab_size=64, hidden=64,
                               num_layers=4, num_heads=4, mlp_dim=256,
                               max_len=64, dropout=0.0)
    model = model_from_json(spec)
    params = model.init(jax.random.PRNGKey(0))

    import sparkflow_tpu.nn as nn
    from sparkflow_tpu.graph_utils import build_graph

    def mlp_graph():
        x = nn.placeholder([None, 8], name="x")
        h = nn.dense(x, 16, activation="relu")
        nn.mean_squared_error(x, nn.dense(h, 4, name="out"))

    rs = np.random.RandomState(0)
    weights = [rs.randn(8, 16).astype(np.float32),
               rs.randn(16).astype(np.float32),
               rs.randn(16, 4).astype(np.float32),
               rs.randn(4).astype(np.float32)]

    def boot_predict(exe_dir):
        t0 = time.perf_counter()
        eng = InferenceEngine(build_graph(mlp_graph), weights,
                              input_name="x:0",
                              output_name="out/BiasAdd:0", max_batch=8,
                              executable_dir=exe_dir)
        eng.predict(np.zeros((3, 8), np.float32))
        return time.perf_counter() - t0, eng

    def boot_decode(exe_dir):
        t0 = time.perf_counter()
        eng = DecodeEngine(model, params, num_slots=4, page_size=8,
                           num_pages=64, seed=0, metrics=Metrics(),
                           executable_dir=exe_dir)
        info = eng.prefill([5, 9, 2], max_new_tokens=2, temperature=0.0)
        eng.step()
        eng.release(info["slot"])
        return time.perf_counter() - t0, eng

    exe_dir = tempfile.mkdtemp(prefix="coldstart_bench_")
    try:
        boot_predict(exe_dir)          # populate (compile + save)
        boot_decode(exe_dir)
        p_cold_s, _ = boot_predict(None)         # full-compile boots
        d_cold_s, _ = boot_decode(None)
        p_warm_s, p_eng = boot_predict(exe_dir)  # serialized boots
        d_warm_s, d_eng = boot_decode(exe_dir)
        p_loads = p_eng.stats()["cold_start"]["serialized_loads"]
        d_loads = d_eng.stats()["cold_start"]["serialized_loads"]
    finally:
        shutil.rmtree(exe_dir, ignore_errors=True)

    # the claim: serialized boot is measurably below full-compile boot
    ok = (p_warm_s < p_cold_s and d_warm_s < d_cold_s
          and p_loads > 0 and d_loads > 0)
    out = {
        "metric": "cold_start_boot",
        "predict_compile_boot_s": round(p_cold_s, 4),
        "predict_serialized_boot_s": round(p_warm_s, 4),
        "predict_speedup": round(p_cold_s / max(p_warm_s, 1e-9), 2),
        "predict_serialized_loads": int(p_loads),
        "decode_compile_boot_s": round(d_cold_s, 4),
        "decode_serialized_boot_s": round(d_warm_s, 4),
        "decode_speedup": round(d_cold_s / max(d_warm_s, 1e-9), 2),
        "decode_serialized_loads": int(d_loads),
        "serialized_faster": bool(ok),
        "platform": "cpu",
    }
    print(json.dumps(out))


if __name__ == "__main__":
    if "--span-overhead" in sys.argv:
        span_overhead_main()
    elif "--trace-overhead" in sys.argv:
        trace_overhead_main()
    elif "--decode-throughput" in sys.argv:
        decode_throughput_main()
    elif "--prefix-cache" in sys.argv:
        prefix_cache_main()
    elif "--spec-decode" in sys.argv:
        spec_decode_main()
    elif "--kv-quant" in sys.argv:
        kv_quant_main()
    elif "--hot-swap" in sys.argv:
        hot_swap_main()
    elif "--tp-decode" in sys.argv:
        tp_decode_main()
    elif "--pp-decode" in sys.argv:
        pp_decode_main()
    elif "--elastic-straggler" in sys.argv:
        elastic_straggler_main()
    elif "--dp-zero2" in sys.argv:
        dp_zero2_main()
    elif "--dp-zero3" in sys.argv:
        dp_zero3_main()
    elif "--sim" in sys.argv:
        sim_main()
    elif "--cold-start" in sys.argv:
        cold_start_main()
    else:
        main()
