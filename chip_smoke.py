#!/usr/bin/env python3
"""The quickest proof that sparkflow-tpu still starts on the chip.

One process, one import of JAX, three phases through the entry points a user
calls, at GPT-2 small's published widths (Hugging Face ``openai-community/gpt2``
``config.json``: vocab 50257, hidden 768, 12 layers, 12 heads, MLP 3072, 1024
positions), weights from a seed:

1. *estimator* — the README quick start: ``SparkAsyncDL.fit`` on ``localml``,
   transform, save, load, transform again.
2. *train* — ``Trainer.fit`` on the registry ``transformer_lm``, sequence 1024,
   bf16 compute, adam; the flash kernel must be on the path and the step
   traced once.
3. *serve* — an ``InferenceServer`` over a ``ContinuousBatcher`` over a
   ``DecodeEngine`` answering concurrent ``POST /v1/generate`` requests; then
   a short pass with an int8 KV pool and one with speculation. Every pass must
   have traced the pallas paged kernels, and every greedy token must be the
   argmax of an independent reference forward (within a stated tolerance).

Each phase prints one JSON line of observations — not benchmark results. The
last line is ``{"ok": true, "device": {...}}``; any failed check makes the run
exit non-zero with ``"ok": false``. Without ``--rehearse`` a backend that is
not a TPU is a failure. ``--rehearse`` runs the same phases at toy widths on
whatever backend is present; ``--multichip`` runs only the train phase on one
device and on a dp=4 mesh and compares them.
"""

from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import tempfile
import threading
import time

GPT2_SMALL = dict(vocab_size=50257, hidden=768, num_layers=12, num_heads=12,
                  mlp_dim=3072, max_len=1024)

# what one run is made of; REHEARSE keeps every code path and shrinks sizes
REAL = dict(
    widths=GPT2_SMALL, compute_dtype="bfloat16",
    est_rows=3000, est_iters=50,
    train_batch=8, train_steps=8, learning_rate=3e-4,
    slots=8, page=16, chunk=128, budget=16, max_seq=1024, short_max_seq=256,
    prompt_lens=(16, 90, 333, 700), shared_prefix=512,
    short_prompt_lens=(16, 90, 200), spec_k=4,
    # bf16 rounds two correct attention paths a few 1e-2 apart at the logits
    logit_tol=0.1, quant_tol=0.05, loss_tol=0.05)
REHEARSE = dict(
    widths=dict(vocab_size=97, hidden=32, num_layers=2, num_heads=4,
                mlp_dim=64, max_len=128),
    compute_dtype=None,
    est_rows=200, est_iters=40,
    train_batch=4, train_steps=8, learning_rate=3e-3,
    slots=4, page=16, chunk=16, budget=6, max_seq=64, short_max_seq=32,
    prompt_lens=(4, 11, 23, 50), shared_prefix=32,
    short_prompt_lens=(4, 11, 23), spec_k=4,
    logit_tol=2e-3, quant_tol=0.05, loss_tol=1e-3)

SEED = 0


def emit(**line) -> dict:
    print(json.dumps(line), flush=True)
    return line


def check(cond, message: str) -> None:
    if not cond:
        raise AssertionError(message)


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def free_device_memory() -> None:
    import jax
    gc.collect()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# phase 1: the README quick start
# ---------------------------------------------------------------------------


def phase_estimator(cfg) -> dict:
    import numpy as np

    import sparkflow_tpu.nn as nn
    from sparkflow_tpu.graph_utils import build_adam_config, build_graph
    from sparkflow_tpu.localml import (LocalSession, OneHotEncoder, Pipeline,
                                       PipelineModel, VectorAssembler)
    from sparkflow_tpu.pipeline_util import PysparkPipelineWrapper
    from sparkflow_tpu.tensorflow_async import SparkAsyncDL

    def small_model():
        x = nn.placeholder([None, 784], name='x')
        y = nn.placeholder([None, 10], name='y')
        h = nn.dense(x, 256, activation='relu')
        h = nn.dense(h, 256, activation='relu')
        out = nn.dense(h, 10)
        nn.argmax(out, 1, name='out')
        nn.softmax_cross_entropy(y, out)

    rs = np.random.RandomState(SEED)
    labels = rs.randint(0, 10, cfg["est_rows"])
    pixels = rs.rand(cfg["est_rows"], 784) * (0.3 + 0.07 * labels[:, None])
    spark = LocalSession.builder.master('local[4]').getOrCreate()
    cols = [f"_c{i}" for i in range(785)]
    df = spark.createDataFrame(
        [(int(l), *p.tolist()) for l, p in zip(labels, pixels)], cols)

    est = SparkAsyncDL(
        inputCol='features', tensorflowGraph=build_graph(small_model),
        tfInput='x:0', tfLabel='y:0', tfOutput='out:0',
        tfOptimizer='adam',
        optimizerOptions=build_adam_config(learning_rate=1e-3),
        miniBatchSize=300, iters=cfg["est_iters"], labelCol='labels',
        predictionCol='predicted')
    stages = [VectorAssembler(inputCols=cols[1:], outputCol='features'),
              OneHotEncoder(inputCol='_c0', outputCol='labels',
                            dropLast=False),
              est]

    t0 = time.perf_counter()
    fitted = Pipeline(stages=stages).fit(df)
    fit_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    before = [float(r['predicted']) for r in fitted.transform(df).collect()]
    transform_s = time.perf_counter() - t0
    with tempfile.TemporaryDirectory() as tmp:
        path = f"{tmp}/my_pipeline"
        fitted.write().overwrite().save(path)
        loaded = PysparkPipelineWrapper.unwrap(PipelineModel.load(path))
        after = [float(r['predicted']) for r in loaded.transform(df).collect()]

    check(len(before) == cfg["est_rows"], "transform dropped rows")
    check(before == after,
          "predictions differ after the save/load round trip")
    accuracy = float(np.mean(np.asarray(before) == labels))
    check(accuracy > 0.3, f"fit did not learn: train accuracy {accuracy:.3f}")
    return emit(phase="estimator", model="nn 784-256-256-10",
                attention_path=None, rows=cfg["est_rows"],
                iters=cfg["est_iters"], fit_seconds=fit_s,
                transform_seconds=transform_s, train_accuracy=accuracy,
                roundtrip_equal=True, peak_bytes=peak_bytes())


# ---------------------------------------------------------------------------
# phase 2: Trainer.fit on the registry transformer_lm
# ---------------------------------------------------------------------------


def _trace_counts(report: str) -> dict:
    return {name: int(n) for name, n in
            re.findall(r"^(\S+): (\d+) trace\(s\)", report or "", re.M)}


def phase_train(cfg, mesh=None) -> dict:
    import numpy as np

    from sparkflow_tpu.models import build_registry_spec
    from sparkflow_tpu.ops.attention import last_attention_path
    from sparkflow_tpu.trainer import Trainer

    w = cfg["widths"]
    spec = build_registry_spec("transformer_lm", dropout=0.0, **w)
    batch, steps, seq = cfg["train_batch"], cfg["train_steps"], w["max_len"]
    tokens = np.random.RandomState(SEED).randint(
        0, w["vocab_size"], (batch, seq)).astype(np.float32)

    # one batch, swept `steps` times: every step repeats it
    trainer = Trainer(spec, "input_ids", None, optimizer="adam",
                      learning_rate=cfg["learning_rate"], iters=steps,
                      mini_batch_size=batch, shuffle_per_iter=False,
                      compute_dtype=cfg["compute_dtype"], seed=SEED,
                      mesh=mesh, debug_recompiles=True)
    first = trainer.fit(tokens)                  # compiles, then runs
    path = last_attention_path()
    traces = _trace_counts(trainer.recompile_report)
    steady = trainer.fit(tokens, init_params=trainer.params)
    retraces = _trace_counts(trainer.recompile_report)

    losses = [float(l) for l in first.losses]
    check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    check(all(np.isfinite(losses + list(steady.losses))),
          f"non-finite loss: {losses} {steady.losses}")
    check(losses[-1] < losses[0],
          f"loss on a repeated batch did not fall: {losses}")
    check(path == "pallas",
          f"the train step traced attention path {path!r}, not 'pallas'")
    check(traces and all(n == 1 for n in traces.values())
          and not trainer.recompile_findings,
          f"the step did not trace exactly once: {traces}")
    check(not retraces, f"the second fit traced again: {retraces}")

    line = dict(phase="train", model="transformer_lm", widths=w,
                compute_dtype=cfg["compute_dtype"], batch=batch, seq=seq,
                steps=steps, attention_path=path,
                compile_seconds=first.wall_time_s - steady.wall_time_s,
                step_seconds=steady.wall_time_s / steps,
                traces=traces, traces_after_first_fit=retraces,
                losses=losses, peak_bytes=peak_bytes())
    if mesh is not None:
        line.update(_check_spans_mesh(trainer, mesh, tokens, steps))
    del trainer, first, steady
    free_device_memory()
    return emit(**line)


def _check_spans_mesh(trainer, mesh, tokens, steps) -> dict:
    """State and batch really span the mesh, and the step all-reduces.
    Code that has only ever seen one chip may put everything on the first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = mesh.size
    for name, tree in (("parameters", trainer.params),
                       ("optimizer state", trainer._last_opt_state)):
        for leaf in jax.tree.leaves(tree):
            check(len(leaf.sharding.device_set) == n,
                  f"{name} leaf {leaf.shape} sits on "
                  f"{len(leaf.sharding.device_set)} of {n} devices")
    # the fit's own compiled program, lowered again on the arguments it ran
    # on (the persistent cache serves the compile)
    (epoch_fn,) = trainer._epoch_cache.values()
    rows = tokens.shape[0]
    compiled = epoch_fn.lower(
        trainer.params, trainer._last_opt_state, jnp.asarray(tokens),
        jnp.zeros((rows, 1), jnp.float32), jnp.ones((rows,), jnp.float32),
        jnp.zeros((steps, 2), jnp.uint32)).compile()
    batch_sharding = compiled.input_shardings[0][2]
    check(len(batch_sharding.device_set) == n
          and not batch_sharding.is_fully_replicated,
          f"the batch is not sharded over {n} devices: {batch_sharding}")
    text = compiled.as_text()
    check("all-reduce" in text, "the compiled step holds no all-reduce")
    return dict(mesh=dict(mesh.shape), zero_stage=trainer._zero_stage,
                batch_sharding=str(batch_sharding.spec),
                all_reduces=text.count("all-reduce("),
                reduce_scatters=text.count("reduce-scatter("))


# ---------------------------------------------------------------------------
# phase 3: InferenceServer / ContinuousBatcher / DecodeEngine
# ---------------------------------------------------------------------------


class _NoPredictPlane:
    """``InferenceServer`` wants a predict engine; this smoke only generates."""
    max_batch = 1

    def predict(self, x):
        return x


def _prompts(cfg, lens, rs, shared_prefix=0):
    vocab = cfg["widths"]["vocab_size"]
    prompts = [rs.randint(0, vocab, n).tolist() for n in lens]
    if shared_prefix:
        prefix = rs.randint(0, vocab, shared_prefix).tolist()
        prompts += [prefix + rs.randint(0, vocab, 5 + i).tolist()
                    for i in range(2)]
    return prompts


def _reference_logits_fn(model, params):
    """The model's plain forward, traced under ``force_xla_attention()``:
    logits ``[max_len, vocab]`` for one padded sequence. Causal attention
    keeps rows before the padding independent of it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkflow_tpu.ops.attention import force_xla_attention

    @jax.jit
    def forward(params, ids):
        return model.apply(params, {"input_ids": ids}, ["logits"])["logits"][0]

    def logits(sequence, start, n):
        """Rows ``start .. start+n`` of the logits over ``sequence``."""
        ids = jnp.zeros((1, model.max_len), jnp.int32).at[
            0, :len(sequence)].set(jnp.asarray(sequence, jnp.int32))
        with force_xla_attention():
            return np.asarray(forward(params, ids)[start:start + n],
                              np.float32)

    return logits


def _serve_pass(cfg, label, model, params, reference_logits, prompts, tol,
                shared=None, **engine_kw) -> dict:
    """One engine, one server, one burst. ``shared`` is the index of a
    prompt to answer before the burst (its prefix twin comes last)."""
    import numpy as np

    from sparkflow_tpu.serving import (ContinuousBatcher, DecodeEngine,
                                       InferenceServer, ServingClient)

    budget = cfg["budget"]
    t0 = time.perf_counter()
    engine = DecodeEngine(model, params, num_slots=cfg["slots"],
                          page_size=cfg["page"], prefill_chunk=cfg["chunk"],
                          prefix_cache=True, seed=SEED, **engine_kw)
    compile_s = time.perf_counter() - t0
    server = InferenceServer(
        _NoPredictPlane(), generate_batcher=ContinuousBatcher(engine),
        request_timeout_s=600.0, drain_timeout_s=60.0).start()
    results, errors = {}, []

    def ask(i: int) -> None:
        # even requests greedy, odd ones seeded-sampled
        greedy = i % 2 == 0
        client = ServingClient(server.url, timeout=600, retries=0)
        try:
            results[i] = client.generate(
                prompts[i], max_new_tokens=budget,
                temperature=0.0 if greedy else 0.8,
                top_k=0 if greedy else 40,
                seed=None if greedy else 1000 + i,
                request_id=f"{label}-{i}")
        except Exception as exc:  # noqa: BLE001 - reported below, fails the phase
            errors.append((i, repr(exc)))
        finally:
            client.close()

    try:
        # the first shared-prefix prompt lands before the burst, so its twin
        # finds the prefix committed
        if shared is not None:
            ask(shared)
        t0 = time.perf_counter()
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts)) if i != shared]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        burst_s = time.perf_counter() - t0
        client = ServingClient(server.url, timeout=60, retries=0)
        health = client.healthz()
        metrics = client.metrics()
        client.close()
    finally:
        server.stop()

    check(not errors, f"{label}: requests failed: {errors}")
    check(sorted(results) == list(range(len(prompts))),
          f"{label}: answered {sorted(results)} of {len(prompts)} requests")
    for i, r in results.items():
        check(len(r["tokens"]) == budget,
              f"{label}: request {i} got {len(r['tokens'])} tokens, "
              f"budget {budget}")
    check(health["status"] == "ok", f"{label}: /healthz says {health}")
    check(bool(metrics), f"{label}: /metrics answered nothing")

    stats = health["decode"]["engine"]
    paths = stats["attention_paths"]
    paged = {exe: p for exe, ps in paths.items() for p in ps
             if p.startswith("paged_attention")}
    check(paged and all(p.endswith(":pallas") for p in paged.values()),
          f"{label}: paged attention left the pallas kernel: {paths}")
    check(any(p.startswith("paged_attention:") for p in paths.get("step", ())),
          f"{label}: the decode step traced no paged kernel: {paths}")
    if engine_kw.get("spec_k"):
        check("paged_attention_verify:pallas" in paths.get("verify", ()),
              f"{label}: the verify step is off the pallas kernel: {paths}")
    check(stats["steady_traces"] == 0,
          f"{label}: {stats['steady_traces']} traces after warmup")
    if shared is not None:
        check(stats["kv"]["prefix_hits"] > 0,
              f"{label}: the shared prefix was never hit: {stats['kv']}")

    # every greedy token against the reference forward: its reference logit
    # within `tol` of the reference maximum (exact argmax but for ties that
    # low-precision rounding breaks either way)
    worst, exact, total = 0.0, 0, 0
    for i, r in results.items():
        if i % 2:
            continue
        rows = reference_logits(prompts[i] + r["tokens"],
                                len(prompts[i]) - 1, budget)
        check(np.isfinite(rows).all(), f"{label}: non-finite reference logits")
        gaps = rows.max(axis=1) - rows[np.arange(budget), r["tokens"]]
        worst = max(worst, float(gaps.max()))
        exact += int((gaps == 0).sum())
        total += budget
    check(worst <= tol,
          f"{label}: a greedy token sits {worst:.4f} logits under the "
          f"reference argmax (tolerance {tol})")

    tokens_out = sum(len(r["tokens"]) for r in results.values())
    line = dict(phase="serve", variant=label, model="transformer_lm",
                widths=cfg["widths"], compute_dtype=cfg["compute_dtype"],
                engine=dict(engine_kw, slots=cfg["slots"], page=cfg["page"],
                            prefill_chunk=cfg["chunk"],
                            max_seq_len=stats["max_seq_len"]),
                attention_path="pallas", attention_paths=paths,
                compile_seconds=compile_s, requests=len(prompts),
                prompt_lens=[len(p) for p in prompts], tokens_out=tokens_out,
                burst_seconds=burst_s,
                seconds_per_token=burst_s / tokens_out,
                traces=stats["traces"], steady_traces=stats["steady_traces"],
                prefix_hits=stats["kv"]["prefix_hits"],
                greedy_argmax_exact=f"{exact}/{total}",
                greedy_worst_logit_gap=worst, logit_tolerance=tol,
                spec=stats["spec"] if engine_kw.get("spec_k") else None,
                kv_quant_error=stats["kv_quant_error"],
                peak_bytes=peak_bytes())
    del engine, server
    gc.collect()
    return emit(**line)


def phase_serve(cfg) -> list:
    import jax
    import numpy as np

    from sparkflow_tpu.models import build_registry_spec, model_from_json

    model = model_from_json(
        build_registry_spec("transformer_lm", dropout=0.0, **cfg["widths"]),
        compute_dtype=cfg["compute_dtype"])
    params = model.init(jax.random.PRNGKey(SEED))
    reference_logits = _reference_logits_fn(model, params)
    rs = np.random.RandomState(SEED + 1)
    tol, short = cfg["logit_tol"], cfg["short_max_seq"]
    lines = [
        _serve_pass(cfg, "bf16 pool, shared-prefix burst", model, params,
                    reference_logits,
                    _prompts(cfg, cfg["prompt_lens"], rs,
                             cfg["shared_prefix"]), tol,
                    shared=len(cfg["prompt_lens"]), max_seq_len=cfg["max_seq"]),
        _serve_pass(cfg, "int8 kv pool", model, params, reference_logits,
                    _prompts(cfg, cfg["short_prompt_lens"], rs),
                    tol + cfg["quant_tol"], kv_quant="int8",
                    max_seq_len=short),
        _serve_pass(cfg, "speculative", model, params, reference_logits,
                    _prompts(cfg, cfg["short_prompt_lens"], rs), tol,
                    spec_k=cfg["spec_k"], max_seq_len=short),
    ]
    del params, reference_logits
    free_device_memory()
    return lines


# ---------------------------------------------------------------------------
# --multichip: the train phase on one device and on dp=4
# ---------------------------------------------------------------------------


def phase_multichip(cfg, one=None) -> dict:
    """``one`` is the one-device train line, where it has already run."""
    import jax
    import numpy as np

    from sparkflow_tpu.parallel.mesh import make_mesh

    check(len(jax.devices()) >= 4,
          f"--multichip needs 4 devices, JAX reports {len(jax.devices())}")
    one = one or phase_train(cfg)
    four = phase_train(cfg, mesh=make_mesh({"dp": 4},
                                           devices=jax.devices()[:4]))
    delta = float(np.max(np.abs(np.asarray(one["losses"])
                                - np.asarray(four["losses"]))))
    check(delta <= cfg["loss_tol"],
          f"dp=4 losses leave the one-device fit by {delta:.5f} "
          f"(tolerance {cfg['loss_tol']}): {one['losses']} vs "
          f"{four['losses']}")
    return emit(phase="multichip", max_loss_delta=delta,
                loss_tolerance=cfg["loss_tol"], mesh=four["mesh"],
                zero_stage=four["zero_stage"])


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on whatever backend is present")
    ap.add_argument("--multichip", action="store_true",
                    help="only the train phase: one device against dp=4")
    args = ap.parse_args(argv)
    device = None
    try:
        from sparkflow_tpu.utils.hw import enable_compilation_cache
        cache_dir = enable_compilation_cache()

        import jax
        cache_events = {"hits": 0, "misses": 0}

        def count(event, **_):
            if event.endswith("/compilation_cache/cache_hits"):
                cache_events["hits"] += 1
            elif event.endswith("/compilation_cache/cache_misses"):
                cache_events["misses"] += 1

        jax.monitoring.register_event_listener(count)
        d = jax.devices()[0]
        device = dict(platform=d.platform, kind=d.device_kind,
                      count=len(jax.devices()))
        check(args.rehearse or d.platform == "tpu",
              f"no TPU: JAX reports platform {d.platform!r} "
              f"(--rehearse runs toy widths anywhere)")

        from sparkflow_tpu.native.build import load_library
        emit(phase="setup", rehearse=args.rehearse, compile_cache_dir=cache_dir,
             native_library_built=load_library() is not None, device=device)
        cfg = REHEARSE if args.rehearse else REAL
        if args.multichip:
            phase_multichip(cfg)
        else:
            phase_estimator(cfg)
            free_device_memory()
            phase_train(cfg)
            phase_serve(cfg)
        emit(phase="compile_cache", dir=cache_dir, **cache_events)
    except Exception as exc:  # noqa: BLE001 - the boundary: reported, then exit 1
        import traceback
        traceback.print_exc()
        emit(ok=False, error=f"{type(exc).__name__}: {exc}"[:2000],
             device=device)
        return 1
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
