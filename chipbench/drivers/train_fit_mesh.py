"""Driver ``train_fit_mesh``: ``train_fit``'s window and comparison for a
``Trainer`` built from the configuration's own keys.

``train_fit.build_trainer`` knows the GPT-2 family's keys. This driver keeps
its window (whole fused ``Trainer.fit`` calls back to back), its first call
that is both warm-up and checked output, and its comparison against the
reference, and brings three things of its own:

- ``build_trainer``: the registry arguments are the configuration file's
  ``registry_config`` (the constructor's keywords, in the file's own words).
- the model's counters (``TrainResult.metrics``) of **the calls a metric's
  time comes from**: every ``Trainer.fit`` call's counters are kept in order
  (``counted_fits``), and after the window ``run.counters["model_metrics"]``
  holds the means over the steps of the traced calls (of every window call
  in an untraced run) and ``run.counters["counted_calls"]`` says which calls
  of the window those were. The first call's own go into its ``first_call``
  line and nowhere else: routing moves from call to call.
- ``window``: in a traced run of a mix that sets ``trace_scopes``, device
  time by the scope an operation was traced in is read from the trace before
  the harness deletes it (``run.counters["scope_seconds"]``,
  ``chipbench/op_scopes.py``'s reader).

Traffic file: ``train_fit``'s keys, and optionally ``trace_scopes`` and
``reference_query_block`` (the reference computes a row's scores a block of
queries at a time). The records call this driver ``train_fit_mesh`` after the
four-chip mix ISSUE 28 queued with it; that mix and its mesh are not here
(PERF.md section 7, question 2).
"""

from __future__ import annotations

import gc
import re
import time

import numpy as np

from chipbench import op_scopes, trace_reduce, traffic
from chipbench.drivers import train_fit
from chipbench.drivers.train_fit import (_trace_counts, batch_schedule,
                                         compare_numbers, program_first_call)

__all__ = ["setup", "window", "compare", "readings", "build_trainer"]


def build_trainer(run):
    from sparkflow_tpu.models import build_registry_spec
    from sparkflow_tpu.trainer import Trainer

    cfg = run.cfg
    spec = build_registry_spec(cfg["registry_model"], **cfg["registry_config"])
    return Trainer(spec, "input_ids", None,
                   compute_dtype=cfg["compute_dtype"],
                   seed=run.seed % (2 ** 31 - 1), **run.mix["trainer"])


def counted_fits(trainer) -> list:
    """Keep the counters of every ``trainer.fit`` call from now on
    (``TrainResult.metrics``: ``{name: [epochs, steps, ...]}``, or ``None``
    from a model that has none), in the calls' order, in the list returned.
    The wrapper hangs on the trainer and holds it: :func:`release` takes it
    off again."""
    kept, fit = [], trainer.fit

    def counted(*args, **kw):
        res = fit(*args, **kw)
        kept.append(getattr(res, "metrics", None))
        return res

    trainer.fit = counted
    return kept


def release(trainer) -> None:
    """Undo :func:`counted_fits`, so that dropping the last name of the
    trainer frees its weights and optimizer state at once. With the wrapper
    on, trainer and wrapper hold each other, and only the cycle collector
    could free them: ``run.main`` freezes it after set-up
    (``gc.freeze()``), the 10 GB stayed on the chip and the reference's step
    could not be loaded (my chip run, PR 28)."""
    vars(trainer).pop("fit", None)


def step_means(calls) -> dict:
    """The counters of some fit calls as means over all their steps."""
    calls = [m for m in calls if m]
    return {k: np.concatenate([
        np.asarray(m[k], np.float64).reshape((-1,) + np.shape(m[k])[2:])
        for m in calls]).mean(axis=0).tolist() for k in (calls[0] if calls
                                                        else {})}


def setup(run) -> dict:
    import jax

    mix, cfg, ref = run.mix, run.cfg, run.reference
    tokens = traffic.train_rows(mix, run.seed, cfg["vocab_size"])
    steps = len(batch_schedule(mix, tokens))
    rows = tokens.astype(np.float32)         # Trainer.fit's own feed type
    p0 = ref.init_params(cfg, run.seed)
    jax.block_until_ready(p0)
    run.phase("weights")

    trainer = build_trainer(run)
    fits = counted_fits(trainer)
    run.phase("trainer_build")

    first = program_first_call(run, trainer, rows, p0)
    del p0
    run.phase("first_call")
    run.log("first_call", seconds=first["seconds"],
            wall_time_s=first["wall_time_s"], losses=first["losses"],
            steps_per_call=steps, model_metrics=step_means(fits),
            traces=_trace_counts(trainer.recompile_report))
    return dict(trainer=trainer, rows=rows, tokens=tokens, first=first,
                steps_per_call=steps, fits=fits)


_SCOPE_PARTS = re.compile(r"[/()]+")


def scope_seconds(xplane: str) -> dict:
    """Device seconds of the busiest program's operations by every scope
    name on their paths, summed over the devices, and the devices' number:
    ``{"devices": n, "seconds": {scope: s}}``. An operation counts under
    each name on its path (``loss``, ``indexer``, ...), a fusion under its
    root's path."""
    devices = op_scopes.read(xplane)
    _, ops = op_scopes.program_ops(devices)
    seconds: dict = {}
    for s, _, path in ops:
        for name in set(_SCOPE_PARTS.split(path)):
            if name:
                seconds[name] = seconds.get(name, 0.0) + s
    return {"devices": len(devices), "seconds": seconds}


def window(run, state) -> dict:
    fits, before = state["fits"], len(state["fits"])
    reduce_trace = run.reduce_trace

    def keep_scopes(trace_dir, span=None):
        # the harness deletes the trace once it has each event's time and
        # text: read the scope paths first
        try:
            run.counters["scope_seconds"] = scope_seconds(
                trace_reduce.find_xplane(trace_dir))
        except (ValueError, FileNotFoundError):   # no program on a TPU plane
            pass
        return reduce_trace(trace_dir, span)

    if run.mix.get("trace_scopes"):
        run.reduce_trace = keep_scopes
    try:
        out = train_fit.window(run, state)
    finally:
        run.reduce_trace = reduce_trace
    # train_fit.window traces its calls 1 .. trace_calls; the counters have to
    # be of the calls whose time the metrics read
    calls = list(range(len(fits) - before))
    if run.trace:
        calls = calls[1:1 + int(run.mix.get("trace_calls", 2))]
    run.counters["counted_calls"] = calls
    run.counters["model_metrics"] = step_means(
        [fits[before + i] for i in calls])
    return out


def reference_call(run, tokens, matmul=None) -> dict:
    ref, mix = run.reference, run.mix
    kw = {} if matmul is None else {"matmul": matmul}
    if "reference_query_block" in mix:
        kw["query_block"] = int(mix["reference_query_block"])
    fresh = lambda: ref.init_params(run.cfg, run.seed)
    # ``again``: the reference is too large to keep two copies of its weights
    out = ref.train_steps(
        fresh(), batch_schedule(mix, tokens), run.cfg, again=fresh,
        learning_rate=float(mix["trainer"]["learning_rate"]),
        row_block=int(mix["reference_row_block"]), **kw)
    out["losses"] = [float(np.mean(epoch)) for epoch in np.split(
        np.asarray(out["losses"]), int(mix["trainer"]["iters"]))]
    return out


def compare(run, state) -> dict:
    import jax

    tokens, program = state["tokens"], state["first"]
    release(state["trainer"])
    del state["trainer"], state["rows"]
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    reference = reference_call(run, tokens)
    out = compare_numbers(run, program, reference)
    run.log("compare", seconds=time.perf_counter() - t0, numbers=out)
    return {k: {"value": v["value"], "limit": v["limit"]}
            for k, v in out.items()}


def readings(runs, control_seeds) -> None:
    """For ``chipbench/control.py``: as ``train_fit.readings``, with this
    driver's trainer, first call and reference."""
    import jax

    trainer, firsts = build_trainer(runs[0]), []
    fits = counted_fits(trainer)
    for run in runs:
        tokens = traffic.train_rows(run.mix, run.seed, run.cfg["vocab_size"])
        p0 = run.reference.init_params(run.cfg, run.seed)
        firsts.append(program_first_call(
            run, trainer, tokens.astype(np.float32), p0))
        del p0
    release(trainer)
    del trainer
    gc.collect()
    jax.clear_caches()
    for run, first, metrics in zip(runs, firsts, fits):
        tokens = traffic.train_rows(run.mix, run.seed, run.cfg["vocab_size"])
        t0 = time.perf_counter()
        reference = reference_call(run, tokens)
        run.log("sound", seed=run.seed, reference_s=time.perf_counter() - t0,
                call_s=first["seconds"], losses=first["losses"],
                model_metrics=step_means([metrics]),
                numbers=compare_numbers(run, first, reference))
        if run.seed in control_seeds:
            t0 = time.perf_counter()
            lower = reference_call(run, tokens,
                                   matmul=run.reference.int8_matmul)
            run.log("control", seed=run.seed,
                    control_s=time.perf_counter() - t0,
                    numbers=compare_numbers(run, lower, reference))
