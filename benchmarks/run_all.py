"""Extended benchmark suite (BASELINE.md's config ladder).

Prints one JSON line per benchmark. ``python benchmarks/run_all.py [--quick]``.
The headline driver metric stays in ``bench.py``; this file tracks the wider
ladder: MLP / CNN / autoencoder (the reference's three example workloads),
ResNet-50 CIFAR, BERT-base seq-512 step time, and the flash-attention kernel
against XLA's naive attention.
"""

import json
import sys
import time

import numpy as np

QUICK = "--quick" in sys.argv


def _emit(name, value, unit, extra=None):
    rec = {"benchmark": name, "value": round(float(value), 2), "unit": unit}
    if extra:
        rec.update(extra)
    print(json.dumps(rec), flush=True)


def _train_eps(graph, input_name, label_name, x, y, batch, epochs, **kw):
    """(examples/sec, mfu-extras dict) for a fused multi-epoch fit.

    FLOPs come from XLA's cost analysis of one train step (these ladder
    models are pure XLA — no pallas custom calls to undercount); MFU keys
    are omitted off-TPU, where a CPU 'peak' would be meaningless."""
    from sparkflow_tpu.trainer import Trainer
    from sparkflow_tpu.utils.flops import (device_peak_flops, mfu,
                                           train_step_flops)

    tr = Trainer(graph, input_name, label_name, optimizer="adam",
                 mini_batch_size=batch, iters=epochs, **kw)
    tr.fit(x, y)  # warmup compiles the same fused multi-epoch program
    res = tr.fit(x, y, init_params=tr.params)
    eps = res.examples_per_sec

    extra = {}
    n = x.shape[0]
    bs = min(batch, n)
    step_fl = train_step_flops(tr.model, input_name, label_name, tr.optimizer,
                               x[:bs], y[:bs] if y is not None else None)
    if step_fl:
        fps = (eps / bs) * step_fl
        extra["tflops_per_sec"] = round(fps / 1e12, 3)
        u = mfu(fps, device_peak_flops())
        if u is not None:
            extra["mfu"] = round(u, 4)
    return eps, extra


def bench_examples_ladder(compute_dtype):
    from sparkflow_tpu.models import presets

    n = 2048 if QUICK else 16384
    rs = np.random.RandomState(0)
    x = rs.rand(n, 784).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, n)]
    epochs = 2 if QUICK else 5

    eps, ex = _train_eps(presets.mlp(784, 10), "x:0", "y:0", x, y, 1024,
                         epochs, compute_dtype=compute_dtype)
    _emit("mnist_mlp_train", eps, "examples/sec", ex)
    eps, ex = _train_eps(presets.cnn(), "x:0", "y:0", x, y, 1024, epochs,
                         compute_dtype=compute_dtype)
    _emit("mnist_cnn_train", eps, "examples/sec", ex)
    eps, ex = _train_eps(presets.autoencoder(784), "x:0", None, x, None,
                         1024, epochs, compute_dtype=compute_dtype)
    _emit("mnist_autoencoder_train", eps, "examples/sec", ex)


def bench_resnet(compute_dtype):
    from sparkflow_tpu.models import build_registry_spec

    n = 256 if QUICK else 2048
    rs = np.random.RandomState(0)
    x = rs.rand(n, 32, 32, 3).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, n)]
    spec = build_registry_spec("resnet", num_classes=10,
                               depth=18 if QUICK else 50, image_size=32,
                               width=16 if QUICK else 64)
    eps, ex = _train_eps(spec, "x:0", "y:0", x, y, 64 if QUICK else 256, 2,
                         compute_dtype=compute_dtype)
    _emit("resnet_cifar_train", eps, "examples/sec",
          {"depth": 18 if QUICK else 50, **ex})


def bench_bert_step(compute_dtype):
    import jax
    import jax.numpy as jnp
    import optax

    from sparkflow_tpu.models import build_registry_spec, model_from_json
    from sparkflow_tpu.optimizers import build_optimizer

    from sparkflow_tpu.utils.flops import (device_peak_flops, mfu,
                                           transformer_train_step_flops)

    if QUICK:
        cfg = dict(vocab_size=1000, hidden=128, num_layers=2, num_heads=4,
                   mlp_dim=256, max_len=128)
        batches = (8,)
    else:
        cfg = dict(vocab_size=30522, hidden=768, num_layers=12, num_heads=12,
                   mlp_dim=3072, max_len=512)
        # batch is the first MFU lever (BASELINE.md fixes model+seq, not
        # batch; the metric is examples/sec/chip) — scan and keep the best
        batches = (16, 32, 64) if jax.default_backend() == "tpu" else (16,)
    m = model_from_json(build_registry_spec("transformer_classifier",
                                            num_classes=2, dropout=0.1, **cfg),
                        compute_dtype=compute_dtype)
    opt = build_optimizer("adam", 1e-4, None)
    rs = np.random.RandomState(0)

    def measure(B):
        params = m.init(jax.random.PRNGKey(0))
        state = opt.init(params)

        @jax.jit
        def step(params, state, ids, y, rng):
            def lf(p):
                return m.loss_vector(p, {"input_ids": ids, "y": y},
                                     train=True, rng=rng).mean()
            loss, g = jax.value_and_grad(lf)(params)
            u, state = opt.update(g, state, params)
            return optax.apply_updates(params, u), state, loss

        def batch(i):
            return (jnp.asarray(rs.randint(0, cfg["vocab_size"],
                                           (B, cfg["max_len"])), jnp.int32),
                    jnp.asarray(np.eye(2)[rs.randint(0, 2, B)], jnp.float32))

        def key(i):
            # hardware PRNG dropout keys on TPU: threefry mask generation is
            # pure VPU overhead on the step (the mfu_sweep 'rbg' variant
            # measures the delta); the headline entry runs the best config
            if jax.default_backend() == "tpu":
                return jax.random.key(i, impl="rbg")
            return jax.random.PRNGKey(i)

        ids, y = batch(0)
        params, state, loss = step(params, state, ids, y, key(0))
        jax.block_until_ready(params)
        if jax.default_backend() == "tpu":
            # fail LOUDLY if the perf path degraded: a kernel edit that broke
            # the TPU tile rules would otherwise fall back silently and this
            # number would quietly measure XLA attention instead
            from sparkflow_tpu.ops.attention import last_attention_path
            path = last_attention_path()
            assert path == "pallas", (
                f"BERT step attention traced to the {path!r} path, not the "
                f"pallas kernel — the flash tile rules rejected this config")
        t0 = time.perf_counter()
        n_steps = 3 if QUICK else 8
        for i in range(n_steps):
            ids, y = batch(i + 1)
            params, state, loss = step(params, state, ids, y, key(i))
        jax.block_until_ready(params)
        return (time.perf_counter() - t0) / n_steps

    results = {B: measure(B) for B in batches}

    # attention runs in pallas here, which XLA's cost analysis counts as
    # zero flops — use the analytic transformer count instead
    def _entry(B):
        dt = results[B]
        step_fl = transformer_train_step_flops(
            B, cfg["max_len"], cfg["hidden"], cfg["num_layers"],
            cfg["mlp_dim"], num_classes=2)
        extra = {"ms_per_step": round(dt * 1e3, 1), "batch": B,
                 "seq": cfg["max_len"],
                 "tflops_per_sec": round(step_fl / dt / 1e12, 3)}
        u = mfu(step_fl / dt, device_peak_flops())
        if u is not None:
            extra["mfu"] = round(u, 4)
        return extra

    # the headline metric stays at the historical fixed batch (B=16) so
    # cross-round and vs-baseline comparisons compare the same config;
    # the batch scan is reported alongside, best batch as its own metric
    B0 = batches[0]
    extra = _entry(B0)
    if len(results) > 1:
        extra["examples_per_sec_by_batch"] = {
            str(b): round(b / t, 2) for b, t in results.items()}
    _emit("bert_seq512_train_step" if not QUICK else "bert_tiny_train_step",
          B0 / results[B0], "examples/sec", extra)
    if len(results) > 1:
        Bb = max(results, key=lambda b: b / results[b])
        if Bb != B0:
            _emit("bert_seq512_train_step_best_batch", Bb / results[Bb],
                  "examples/sec", _entry(Bb))


def bench_flash_attention():
    import jax
    import jax.numpy as jnp

    from sparkflow_tpu.ops import attention_reference, flash_attention

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        # interpret-mode pallas under jit unrolls the whole grid — the number
        # would measure the interpreter, not the kernel
        _emit("flash_attention_vs_xla", 0, "speedup_x", {"skipped": "not on tpu"})
        return
    S = 1024 if QUICK else 4096
    rs = np.random.RandomState(0)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    # Amortize dispatch: lax.scan the op over ITERS pre-stacked inputs inside
    # ONE jit — a single dispatch+sync covers ITERS kernel invocations.
    ITERS = 4 if QUICK else 16

    def _fresh_stack():
        return jax.block_until_ready(
            jnp.asarray(rs.randn(ITERS, 2, 8, S, 64), dtype))

    def _timed(op):
        @jax.jit
        def many(xs):
            def body(acc, q):
                return acc + op(q), None
            out, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
            return out
        float(many(_fresh_stack()))  # compile + warm
        inp = _fresh_stack()
        t0 = time.perf_counter()
        float(many(inp))
        return (time.perf_counter() - t0) / ITERS

    from sparkflow_tpu.utils.flops import attention_flops, device_peak_flops

    peak = device_peak_flops()

    def _kernel_util(flops, secs):
        return ({"kernel_tflops_per_sec": round(flops / secs / 1e12, 2),
                 "kernel_util": round(flops / secs / peak, 4)} if peak else {})

    tf = _timed(lambda q: flash_attention(q, q, q, causal=True).astype(jnp.float32).sum())
    from sparkflow_tpu.ops.attention import last_attention_path
    assert last_attention_path() == "pallas", (
        f"flash bench traced the {last_attention_path()!r} path — the pallas "
        f"kernel was silently rejected for this config")
    tr = _timed(lambda q: attention_reference(q, q, q, causal=True)
                .astype(jnp.float32).sum())
    fwd_fl = attention_flops(2, 8, S, S, 64, causal=True)
    _emit("flash_attention_vs_xla", tr / tf, "speedup_x",
          {"seq": S, "flash_ms": round(tf * 1e3, 2),
           "xla_ms": round(tr * 1e3, 2), **_kernel_util(fwd_fl, tf)})

    # fwd+bwd: the training-path comparison (pallas dq/dk/dv kernels vs
    # XLA autodiff of the dense reference)
    tfg = _timed(lambda q: jax.grad(lambda a: flash_attention(
        a, a, a, causal=True).astype(jnp.float32)
        .sum())(q).astype(jnp.float32).sum())
    trg = _timed(lambda q: jax.grad(lambda a: attention_reference(a, a, a,
        causal=True).astype(jnp.float32).sum())(q).astype(jnp.float32).sum())
    fb_fl = attention_flops(2, 8, S, S, 64, causal=True, with_backward=True)
    _emit("flash_attention_fwd_bwd_vs_xla", trg / tfg, "speedup_x",
          {"seq": S, "flash_ms": round(tfg * 1e3, 2),
           "xla_ms": round(trg * 1e3, 2), **_kernel_util(fb_fl, tfg)})


def bench_flash_long_context():
    """Long-sequence flash entries (8k/16k/32k): the regime the kernel is
    for. XLA comparison uses the blockwise (memory-bounded) attention — the
    dense reference would materialize an [B,H,S,S] score tensor (8 GB at
    32k) and is not a runnable baseline there. TPU-only, amortized timing
    over fresh inputs like bench_flash_attention."""
    import jax
    import jax.numpy as jnp

    from sparkflow_tpu.ops import flash_attention
    from sparkflow_tpu.ops.attention import _blockwise_attention
    from sparkflow_tpu.utils.flops import attention_flops, device_peak_flops

    if jax.default_backend() != "tpu":
        _emit("flash_attention_long_context", 0, "speedup_x",
              {"skipped": "not on tpu"})
        return
    peak = device_peak_flops()
    rs = np.random.RandomState(0)
    seqs = (8192,) if QUICK else (8192, 16384, 32768)
    for S in seqs:
        B, H, D = 1, 8, 64
        ITERS = 4

        def _fresh():
            return jax.block_until_ready(
                jnp.asarray(rs.randn(ITERS, B, H, S, D), jnp.bfloat16))

        def _timed(op):
            @jax.jit
            def many(xs):
                def body(acc, q):
                    return acc + op(q), None
                out, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
                return out
            float(many(_fresh()))  # compile + warm
            inp = _fresh()
            t0 = time.perf_counter()
            float(many(inp))
            return (time.perf_counter() - t0) / ITERS

        tf = _timed(lambda q: flash_attention(q, q, q, causal=True)
                    .astype(jnp.float32).sum())
        from sparkflow_tpu.ops.attention import last_attention_path
        assert last_attention_path() == "pallas", (
            f"long-context bench at seq {S} traced the "
            f"{last_attention_path()!r} path, not the pallas kernel")
        tb = _timed(lambda q: _blockwise_attention(
            q, q, q, None, True, 1.0 / 8.0, block_k=512)
            .astype(jnp.float32).sum())
        fl = attention_flops(B, H, S, S, D, causal=True)
        extra = {"seq": S, "flash_ms": round(tf * 1e3, 2),
                 "xla_blockwise_ms": round(tb * 1e3, 2),
                 "kernel_tflops_per_sec": round(fl / tf / 1e12, 2)}
        if peak:
            extra["kernel_util"] = round(fl / tf / peak, 4)
        _emit("flash_attention_long_context", tb / tf, "speedup_x", extra)


def bench_ring_flash_long_context():
    """Ring-flash sequence-parallel attention at 8k/16k GLOBAL context: the
    sp training path's attention (K/V shards rotating over the ring, pallas
    kernel per visit — ops/attention.py:ring_flash_attention). On one chip
    the ring is a single hop; on a pod slice the same program spans ICI.
    Emits per-chip tokens/sec so multi-chip runs compare per-chip
    efficiency, not just scale. TPU-only; amortized over fresh inputs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from sparkflow_tpu.ops import ring_flash_attention
    from sparkflow_tpu.utils.flops import attention_flops, device_peak_flops

    if jax.default_backend() != "tpu":
        _emit("ring_flash_long_context", 0, "tokens_per_sec_per_chip",
              {"skipped": "not on tpu"})
        return
    peak = device_peak_flops()
    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    rs = np.random.RandomState(0)
    seqs = (8192,) if QUICK else (8192, 16384)
    for S in seqs:
        B, H, D = 1, 8, 64
        ITERS = 4

        def inner(q, k, v):
            o = ring_flash_attention(q, k, v, "sp", causal=True)
            return jax.lax.psum(o.astype(jnp.float32).sum(), "sp")

        ring = jax.shard_map(inner, mesh=mesh,
                         in_specs=(P(None, None, "sp"),) * 3,
                         out_specs=P(), check_vma=False)

        def _fresh():
            return jax.block_until_ready(
                jnp.asarray(rs.randn(ITERS, B, H, S, D), jnp.bfloat16))

        @jax.jit
        def many(xs):
            def body(acc, q):
                return acc + ring(q, q, q), None
            out, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
            return out

        float(many(_fresh()))  # compile + warm
        inp = _fresh()
        t0 = time.perf_counter()
        float(many(inp))
        t = (time.perf_counter() - t0) / ITERS
        fl = attention_flops(B, H, S, S, D, causal=True)
        extra = {"seq": S, "ring_devices": n,
                 "ring_flash_ms": round(t * 1e3, 2),
                 "tflops_per_sec_per_chip": round(fl / t / n / 1e12, 2)}
        if peak:
            extra["kernel_util"] = round(fl / t / n / peak, 4)
        _emit("ring_flash_long_context", round(B * S / t / n, 1),
              "tokens_per_sec_per_chip", extra)


def bench_stream_vs_collect(compute_dtype):
    """fitMode='stream' vs the collect path on the same CNN workload: the
    native batch ring assembles fixed-shape batches concurrently with device
    compute, so streaming examples/sec should stay within ~10% of the fused
    in-memory fit — if it doesn't, the device is idling on host IO."""
    from sparkflow_tpu.models import presets
    from sparkflow_tpu.trainer import Trainer

    n = 2048 if QUICK else 16384
    rs = np.random.RandomState(0)
    x = rs.rand(n, 784).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, n)]
    epochs = 2 if QUICK else 4

    def make_trainer():
        return Trainer(presets.cnn(), "x:0", "y:0", optimizer="adam",
                       mini_batch_size=1024, iters=epochs,
                       compute_dtype=compute_dtype)

    tr = make_trainer()
    tr.fit(x, y)  # compile warmup
    collect_eps = tr.fit(x, y, init_params=tr.params).examples_per_sec

    def rows():
        for i in range(n):
            yield (x[i], y[i])

    ts = make_trainer()
    ts.fit_stream(rows, epochs=1)  # compile warmup (per-step program)
    stream_eps = ts.fit_stream(rows, init_params=ts.params,
                               epochs=epochs).examples_per_sec
    _emit("stream_vs_collect_fit", stream_eps / collect_eps, "ratio",
          {"stream_examples_per_sec": round(stream_eps, 1),
           "collect_examples_per_sec": round(collect_eps, 1)})


def bench_quantized_inference():
    """int8 serving vs f32 on a wide MLP (the shape quantized serving is
    for: weight-HBM-bound batch inference). TPU-only, amortized timing —
    one scan over fresh pre-staged batches per mode."""
    import jax
    import jax.numpy as jnp

    from sparkflow_tpu.graph_utils import build_graph
    from sparkflow_tpu.graphdef import GraphModel
    import sparkflow_tpu.nn as nn_

    if jax.default_backend() != "tpu":
        _emit("int8_inference_vs_f32", 0, "speedup_x", {"skipped": "not on tpu"})
        return

    def wide_mlp():
        x = nn_.placeholder([None, 1024], name="x")
        h = nn_.dense(x, 4096, activation="relu")
        h = nn_.dense(h, 4096, activation="relu")
        h = nn_.dense(h, 4096, activation="relu")
        nn_.dense(h, 16, name="out")

    model = GraphModel.from_json(build_graph(wide_mlp))
    params = model.init(jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    B, ITERS = 256, 16

    def timed(p):
        @jax.jit
        def many(xs):
            def body(acc, xb):
                out = model.apply(p, {"x": xb}, ["out:0"])["out:0"]
                return acc + out.astype(jnp.float32).sum(), None
            tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
            return tot

        def fresh():
            return jax.block_until_ready(jnp.asarray(
                rs.rand(ITERS, B, 1024), jnp.float32))
        float(many(fresh()))  # compile + warm
        inp = fresh()
        t0 = time.perf_counter()
        float(many(inp))
        return (time.perf_counter() - t0) / ITERS

    t_f32 = timed(params)
    results = {}
    for mode in ("weight_only", "dynamic"):
        qp = model.quantize_for_serving(params, mode=mode)
        try:
            results[mode] = timed(qp)
        finally:
            model.quant_mode = None
    _emit("int8_inference_vs_f32", t_f32 / results["weight_only"], "speedup_x",
          {"batch": B, "f32_ms": round(t_f32 * 1e3, 2),
           "weight_only_ms": round(results["weight_only"] * 1e3, 2),
           "dynamic_ms": round(results["dynamic"] * 1e3, 2),
           "dynamic_speedup_x": round(t_f32 / results["dynamic"], 2)})


def bench_serving_throughput():
    """Micro-batched serving engine vs naive per-request apply: the same
    request stream (mixed sizes 1..8 rows) through (a) one jitted apply call
    per request — the no-batching server, every shape pre-warmed so it pays
    dispatch overhead, not compiles — and (b) the AOT bucket engine behind
    the MicroBatcher, requests coalesced under the deadline. Measurable on
    any backend; the per-call overhead being amortized is host-side."""
    import jax

    import sparkflow_tpu.nn as nn_
    from sparkflow_tpu.graph_utils import build_graph
    from sparkflow_tpu.models import model_from_json
    from sparkflow_tpu.serving import InferenceEngine, MicroBatcher

    def mlp():
        x = nn_.placeholder([None, 256], name="x")
        h = nn_.dense(x, 512, activation="relu")
        h = nn_.dense(h, 512, activation="relu")
        nn_.dense(h, 16, name="out")

    rs = np.random.RandomState(0)
    n_req = 64 if QUICK else 512
    sizes = rs.randint(1, 9, n_req)
    reqs = [rs.rand(s, 256).astype(np.float32) for s in sizes]
    total_rows = int(sizes.sum())

    model = model_from_json(build_graph(mlp))
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(model, params, input_name="x:0",
                             output_name="out/BiasAdd:0", max_batch=64)

    naive = jax.jit(lambda p, xb: model.apply(
        p, {"x": xb}, ["out/BiasAdd:0"])["out/BiasAdd:0"])
    for s in sorted(set(sizes.tolist())):
        np.asarray(naive(params, np.zeros((s, 256), np.float32)))
    t0 = time.perf_counter()
    for r in reqs:
        np.asarray(naive(params, r))
    t_naive = time.perf_counter() - t0

    with MicroBatcher(engine, max_delay_ms=1.0, max_queue=8192) as batcher:
        t0 = time.perf_counter()
        futures = [batcher.submit(r) for r in reqs]
        for f in futures:
            f.result()
        t_batched = time.perf_counter() - t0
    _emit("serving_throughput", t_naive / t_batched, "speedup_x",
          {"requests": n_req, "rows": total_rows,
           "batched_rows_per_sec": round(total_rows / t_batched, 1),
           "naive_rows_per_sec": round(total_rows / t_naive, 1),
           "recompiles_after_warmup": engine.fallback_compiles})


def bench_resume_overhead():
    """Crash/resume tax: an uninterrupted checkpointed fit vs the same fit
    crashed mid-run (deterministic fault injection) and restarted through
    ``resilience.run_resilient_fit``. Emits the wall-clock ratio plus a
    bit-identical-params check. Any backend — the tax being measured is
    host-side (checkpoint IO, restore, resume skip-ahead)."""
    import shutil
    import tempfile

    import jax

    from sparkflow_tpu.models import presets
    from sparkflow_tpu.resilience import (RetryPolicy, faults,
                                          run_resilient_fit)
    from sparkflow_tpu.trainer import Trainer

    n = 2048 if QUICK else 8192
    epochs = 6 if QUICK else 12
    rs = np.random.RandomState(0)
    x = rs.rand(n, 784).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, n)]

    def make(d, cb):
        # the loss_callback keeps both runs on the per-epoch loop path, so
        # the comparison isolates the resume tax, not loop-vs-fused dispatch
        return Trainer(presets.mlp(784, 10), "x:0", "y:0", optimizer="adam",
                       mini_batch_size=1024, iters=epochs, seed=7,
                       checkpoint_dir=d, checkpoint_every=2,
                       resume_retries=0, loss_callback=cb)

    d0 = tempfile.mkdtemp(prefix="bench_resume_base_")
    d1 = tempfile.mkdtemp(prefix="bench_resume_crash_")
    try:
        t0 = time.perf_counter()
        base = make(d0, lambda *a: None).fit(x, y)
        t_base = time.perf_counter() - t0

        crash = faults.crash_at(epochs // 2)
        pol = RetryPolicy(max_attempts=4, base_s=0.0, jitter=0.0, seed=0,
                          sleep=lambda _s: None)  # measure work, not backoff
        t0 = time.perf_counter()
        res = run_resilient_fit(make(d1, crash), x, y, max_restarts=2,
                                restart_policy=pol)
        t_crash = time.perf_counter() - t0

        identical = all(np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(jax.tree.map(np.asarray, base.params)),
            jax.tree.leaves(jax.tree.map(np.asarray, res.params))))
        _emit("resume_overhead", t_crash / t_base, "ratio",
              {"uninterrupted_s": round(t_base, 2),
               "crash_resume_s": round(t_crash, 2),
               "crash_epoch": epochs // 2, "epochs": epochs,
               "bit_identical_params": bool(identical)})
    finally:
        shutil.rmtree(d0, ignore_errors=True)
        shutil.rmtree(d1, ignore_errors=True)


def bench_tokenizer():
    """Native C++ WordPiece vs the python fallback — measurable on any host
    (no TPU involved): strings/sec on synthetic text."""
    from sparkflow_tpu.utils.text import WordpieceTokenizer, build_vocab

    rs = np.random.RandomState(0)
    words = ["".join(chr(97 + c) for c in rs.randint(0, 26, rs.randint(2, 10)))
             for _ in range(2000)]
    texts = [" ".join(words[i] for i in rs.randint(0, len(words), 24))
             for _ in range(500 if QUICK else 4000)]
    vocab = build_vocab(texts, max_size=5000)

    results = {}
    for label, use_native in (("native", True), ("python", False)):
        tok = WordpieceTokenizer(vocab, use_native=use_native)
        if label == "native" and tok._native is None:
            results[label] = None
            continue
        t0 = time.perf_counter()
        tok.encode_batch(texts, 64)
        results[label] = len(texts) / (time.perf_counter() - t0)
    if results.get("native"):
        _emit("wordpiece_tokenizer_native_vs_python",
              results["native"] / results["python"], "speedup_x",
              {"native_strings_per_sec": round(results["native"]),
               "python_strings_per_sec": round(results["python"])})
    else:
        _emit("wordpiece_tokenizer_native_vs_python", 0, "speedup_x",
              {"skipped": "no C++ toolchain"})


def bench_dataplane():
    """Native C++ batch-assembly ring vs the python fallback queue — host-side
    streaming throughput (rows/sec), measurable on any machine. The ring is
    what feeds the device in `fitMode='stream'`."""
    import threading

    from sparkflow_tpu.utils import data as D

    n_rows = 20_000 if QUICK else 200_000
    row_dim, bs = 64, 256
    rows = np.random.RandomState(0).rand(n_rows, row_dim).astype(np.float32)
    chunks = [rows[i:i + 1024] for i in range(0, n_rows, 1024)]

    def pump(use_native):
        real_loader = D.load_library
        if not use_native:
            D.load_library = lambda: None
        try:
            q = D.BatchQueue(bs, row_dim, 0, capacity=8, shuffle=True)
        finally:
            D.load_library = real_loader
        if use_native and q._lib is None:
            q.close()
            return None

        def feed():
            for c in chunks:
                q.push(c)
            q.finish()

        t = threading.Thread(target=feed, daemon=True)
        t0 = time.perf_counter()
        t.start()
        seen = 0
        for x, y, mask, n_real in q:
            seen += n_real
        dt = time.perf_counter() - t0
        t.join()
        q.close()
        assert seen == n_rows, (seen, n_rows)
        return n_rows / dt

    native = pump(True)
    python = pump(False)
    if native:
        _emit("dataplane_ring_native_vs_python", native / python, "speedup_x",
              {"native_rows_per_sec": round(native),
               "python_rows_per_sec": round(python)})
    else:
        _emit("dataplane_ring_native_vs_python", 0, "speedup_x",
              {"skipped": "no C++ toolchain"})


def bench_dp_zero1():
    """ZeRO-1 weight-update sharding vs the replicated dp step: step time and
    per-device optimizer-state bytes (expect ~1/dp) on a pure-dp mesh over
    all local devices. One JSON line; skips below 2 devices."""
    import jax
    import jax.numpy as jnp

    from sparkflow_tpu.models import build_registry_spec, model_from_json
    from sparkflow_tpu.optimizers import build_optimizer
    from sparkflow_tpu.optimizers_sharded import (place_zero1_state,
                                                  sharded_update,
                                                  state_bytes_per_device)
    from sparkflow_tpu.parallel.dp import (make_dp_shardmap_train_step,
                                           make_dp_zero1_train_step)
    from sparkflow_tpu.parallel.mesh import make_mesh

    dp = jax.device_count()
    if dp < 2:
        _emit("dp_zero1_vs_replicated", 0, "ratio",
              {"skipped": "needs >= 2 devices"})
        return
    hidden = 128 if QUICK else 512
    layers = 2 if QUICK else 4
    spec = build_registry_spec("transformer_classifier", vocab_size=1000,
                               num_classes=8, hidden=hidden,
                               num_layers=layers, num_heads=8,
                               mlp_dim=4 * hidden, max_len=64, dropout=0.0)
    m = model_from_json(spec)
    opt = build_optimizer("adam", 1e-3, None)
    mesh = make_mesh({"dp": dp})
    B = 8 * dp
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 1000, (B, 64)), jnp.float32)
    y = jnp.asarray(np.eye(8, dtype=np.float32)[rs.randint(0, 8, B)])
    mask = jnp.ones((B,), jnp.float32)
    p0 = m.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)
    steps = 5 if QUICK else 20

    def timed(step, params, state):
        params, state, _ = step(params, state, ids, y, mask, rng)  # compile
        jax.block_until_ready(params)
        t0 = time.perf_counter()
        for _ in range(steps):
            params, state, _ = step(params, state, ids, y, mask, rng)
        jax.block_until_ready(params)
        return (time.perf_counter() - t0) / steps, state

    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    pR = jax.device_put(jax.tree.map(jnp.array, p0), repl)
    sR = jax.device_put(opt.init(pR), repl)
    tR, sR = timed(make_dp_shardmap_train_step(m, opt, mesh, "input_ids", "y"),
                   pR, sR)
    bytesR = state_bytes_per_device(sR)

    pZ = jax.device_put(jax.tree.map(jnp.array, p0), repl)
    sZ = place_zero1_state(sharded_update(opt, dp, "dp").init(pZ), mesh, dp)
    tZ, sZ = timed(make_dp_zero1_train_step(m, opt, mesh, "input_ids", "y"),
                   pZ, sZ)
    bytesZ = state_bytes_per_device(sZ)

    _emit("dp_zero1_vs_replicated", tR / tZ, "step_time_speedup_x",
          {"dp": dp,
           "replicated_step_ms": round(tR * 1e3, 2),
           "zero1_step_ms": round(tZ * 1e3, 2),
           "replicated_opt_state_bytes_per_device": int(bytesR),
           "zero1_opt_state_bytes_per_device": int(bytesZ),
           "opt_state_reduction_x": round(bytesR / max(bytesZ, 1), 2)})


def main():
    import os
    import sys as _sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import jax

    platform = jax.default_backend()
    compute_dtype = "bfloat16" if platform == "tpu" else None
    print(json.dumps({"suite": "sparkflow-tpu-benchmarks",
                      "platform": platform, "quick": QUICK}), flush=True)
    bench_examples_ladder(compute_dtype)
    bench_resnet(compute_dtype)
    bench_bert_step(compute_dtype)
    bench_flash_attention()
    bench_flash_long_context()
    bench_ring_flash_long_context()
    bench_stream_vs_collect(compute_dtype)
    bench_dp_zero1()
    bench_quantized_inference()
    bench_serving_throughput()
    bench_resume_overhead()
    bench_tokenizer()
    bench_dataplane()


if __name__ == "__main__":
    main()
