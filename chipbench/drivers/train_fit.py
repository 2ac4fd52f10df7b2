"""Driver ``train_fit``: whole fused ``Trainer.fit`` calls on one device.

The window is made of whole calls of ``Trainer.fit`` on the fused
multi-epoch path (one device dispatch per call), each started while the
elapsed time is under ``--seconds`` and continuing from the last call's
parameters. The rate is every token of every call over the time from the
window's start to the last call's end.

Set-up builds ONE ``Trainer`` and drives it from the seeded weights through
its first call: the window's own call, compiled program and feed (every row
of the mix, ``mini_batch_size`` a step, ``iters`` sweeps in one dispatch).
That call is both the warm-up and the timed path's output that decides
``correct``: once the window has closed, the reference follows the same
steps on the same rows from the same weights. The same object then makes
the window's calls. The mix keeps ``shuffle_per_iter`` off, so that the
order of a call's batches is the rows' own (which the seed drew) and the
reference needs nothing from the program to follow them.

Traffic file: ``rows``, ``seq_len``, ``trainer`` (keyword arguments of
``Trainer``), ``reference_row_block``, ``trace_calls``, ``limits``.
"""

from __future__ import annotations

import gc
import re
import shutil
import time

import numpy as np

from chipbench import traffic

FIT_SPAN = "chipbench/fit_call"


def _trace_counts(report) -> int:
    return sum(int(n) for n in re.findall(r"^\S+: (\d+) trace\(s\)",
                                          report or "", re.M))


def _adam_second_moment(opt_state):
    """The second-moment tree inside an optax state (``ScaleByAdamState.nu``):
    the decayed sum of the squares of every gradient the optimizer got."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "mu") and hasattr(node, "nu"):
            return node.nu
        if isinstance(node, (tuple, list)):
            stack.extend(node)
    raise LookupError("the optimizer's state holds no Adam moments; the "
                      "comparison reads the gradients from them")


def program_first_call(run, trainer, rows, p0) -> dict:
    """The program's side of the comparison: the window's own call,
    ``trainer.fit`` on every row, from the seeded weights. Returns what
    ``compare_numbers`` holds against the reference, and the call's time."""
    import jax

    ref = run.reference
    t0 = time.perf_counter()
    res = trainer.fit(rows, init_params=p0)
    jax.block_until_ready(res.params)
    seconds = time.perf_counter() - t0
    change = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x - y, a, b))(
        res.params, p0)
    out = dict(losses=[float(l) for l in res.losses], seconds=seconds,
               wall_time_s=res.wall_time_s,
               change_norms=ref.leaf_norms(change, run.cfg),
               energy_norms=ref.leaf_norms(
                   _adam_second_moment(trainer._last_opt_state), run.cfg,
                   of_root=True))
    del change, res
    return out


def batch_schedule(mix: dict, tokens: np.ndarray) -> np.ndarray:
    """``[steps, mini_batch_size, seq_len]``: the batches of one call in the
    order ``Trainer.fit`` takes them with ``shuffle_per_iter`` off: the rows
    in their own order, ``mini_batch_size`` at a time, ``iters`` times."""
    kw = mix["trainer"]
    if kw.get("shuffle_per_iter", True) or len(tokens) % kw["mini_batch_size"]:
        raise ValueError(
            "the reference follows a call's batches in the rows' own order: "
            "the mix has to set shuffle_per_iter false and rows a multiple "
            "of mini_batch_size")
    sweep = tokens.reshape(-1, kw["mini_batch_size"], tokens.shape[1])
    return np.tile(sweep, (int(kw["iters"]), 1, 1))


def build_trainer(run):
    from sparkflow_tpu.models import build_registry_spec
    from sparkflow_tpu.trainer import Trainer

    cfg = run.cfg
    spec = build_registry_spec(
        cfg["registry_model"], dropout=0.0, vocab_size=cfg["vocab_size"],
        hidden=cfg["n_embd"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], mlp_dim=cfg["n_inner"],
        max_len=cfg["n_positions"])
    return Trainer(spec, "input_ids", None,
                   compute_dtype=cfg["compute_dtype"],
                   seed=run.seed % (2 ** 31 - 1), **run.mix["trainer"])


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
    run.phase("trainer_build")

    first = program_first_call(run, trainer, rows, p0)
    del p0              # a third copy of the weights would not fit a call
    run.phase("first_call")
    run.log("first_call", seconds=first["seconds"],
            wall_time_s=first["wall_time_s"], losses=first["losses"],
            steps_per_call=steps,
            traces=_trace_counts(trainer.recompile_report))
    return dict(trainer=trainer, rows=rows, tokens=tokens, first=first,
                steps_per_call=steps)


def window(run, state) -> dict:
    import jax

    trainer, rows = state["trainer"], state["rows"]
    tokens_per_call = rows.shape[0] * rows.shape[1] * run.mix["trainer"]["iters"]
    trace_calls = int(run.mix.get("trace_calls", 2))
    # trace from the second call on; a traced window goes on until it holds
    # the traced calls, however short ``--seconds`` is
    first_traced = 1
    least_calls = first_traced + trace_calls if run.trace else 0
    trace_dir = run.scratch + "/trace"
    profiler_s, tracing, calls, retraces = 0.0, False, [], 0   # profiler_s:
    # the profiler's own start and stop before the last call ended; they are
    # no part of training and come out of the elapsed time
    if run.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

    started, t0 = time.time(), time.perf_counter()
    while (time.perf_counter() - t0 < run.seconds
           or len(calls) < least_calls):
        i = len(calls)
        if run.trace and i == first_traced:
            p0 = time.perf_counter()
            run.start_trace(trace_dir)
            profiler_s += time.perf_counter() - p0
            tracing = True
        c0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(FIT_SPAN):
            res = trainer.fit(rows, init_params=trainer.params)
            jax.block_until_ready(res.params)
        c1 = time.perf_counter()
        calls.append((c0 - t0, c1 - t0, [float(l) for l in res.losses]))
        retraces += _trace_counts(trainer.recompile_report)
        if tracing and i + 1 == first_traced + trace_calls:
            p0 = time.perf_counter()
            jax.profiler.stop_trace()
            stop_s = time.perf_counter() - p0
            run.log("profiler_stop", seconds=stop_s)
            tracing = False
            if time.perf_counter() - t0 < run.seconds:
                profiler_s += stop_s          # a further call follows
    if tracing:
        jax.profiler.stop_trace()
    elapsed = calls[-1][1] - profiler_s
    rate = len(calls) * tokens_per_call / elapsed

    for k, (a, b, losses) in enumerate(calls):
        run.log("fit_call", call=k, start_s=a, end_s=b, seconds=b - a,
                losses=losses)
    ok = all(np.isfinite(l) for _, _, ls in calls for l in ls)
    run.counters.update(
        calls=len(calls), tokens=len(calls) * tokens_per_call,
        elapsed_s=elapsed, profiler_s=profiler_s, retraces=retraces,
        call_seconds=[b - a for a, b, _ in calls],
        tokens_per_step=tokens_per_call // state["steps_per_call"],
        seq_len=int(rows.shape[1]), fit_span=FIT_SPAN)
    if run.trace:
        run.reduce_trace(trace_dir, FIT_SPAN)
    return dict(end_to_end={"train_tokens_per_s": rate},
                attempted=len(calls), failed=0 if ok else len(calls),
                started=started)


def reference_call(run, tokens, matmul=None) -> dict:
    """The reference's side: the same steps on the same rows from the same
    seeded weights, in float32 at the highest precision; an epoch's loss is
    the mean of its steps' losses, as the program reports it."""
    ref, mix = run.reference, run.mix
    kw = {} if matmul is None else {"matmul": matmul}
    out = ref.train_steps(
        ref.init_params(run.cfg, run.seed), batch_schedule(mix, tokens),
        run.cfg, learning_rate=float(mix["trainer"]["learning_rate"]),
        row_block=int(mix["reference_row_block"]), **kw)
    out["losses"] = [float(np.mean(epoch)) for epoch in np.split(
        np.asarray(out["losses"]), int(mix["trainer"]["iters"]))]
    return out


def compare_numbers(run, program: dict, reference: dict) -> dict:
    """Each number compared, beside its limit (the traffic file's
    ``limits``; PERF.md gives the readings each was set from)."""
    ref, limits = run.reference, run.mix["limits"]
    names = ref.leaf_names(run.cfg)
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss_epoch{i + 1}"] = {
            "value": abs(a - b), "limit": limits[f"loss_epoch{i + 1}"],
            "program": a, "reference": b}
    for key in ("energy", "change"):
        norms = f"{key}_norms"
        gaps = ref.leaf_gaps(program[norms], reference[norms])
        leaf = int(np.argmax(gaps))
        out[f"{key}_worst_leaf"] = {
            "value": float(gaps[leaf]), "limit": limits[key],
            "leaf": names[leaf], "program": float(program[norms][leaf]),
            "reference": float(reference[norms][leaf]),
            "median_leaf_gap": float(np.median(gaps))}
    # the change's worst leaf is one whose gradients lie under Adam's epsilon
    # (PERF.md section 2), so its median leaf stands beside it; the energy's
    # median leaf does not tell int8 from bf16 and is only printed
    out["change_median_leaf"] = {
        "value": out["change_worst_leaf"]["median_leaf_gap"],
        "limit": limits["change_median"]}
    return out


def readings(runs, control_seeds) -> None:
    """For ``chipbench/control.py``: what the limits are set from, read in
    one process with no window. One ``Trainer`` makes the window's own first
    call from each run's seeded weights; then, with the trainer freed, the
    reference follows each, and for ``control_seeds`` so does the control:
    the reference computed with int8 matrix products, put in the program's
    place. It has to fail one of the numbers."""
    import jax

    trainer, firsts = build_trainer(runs[0]), []
    for run in runs:
        tokens = traffic.train_rows(run.mix, run.seed, run.cfg["vocab_size"])
        p0 = run.reference.init_params(run.cfg, run.seed)
        firsts.append(program_first_call(
            run, trainer, tokens.astype(np.float32), p0))
        del p0
    del trainer
    gc.collect()
    jax.clear_caches()
    for run, first in zip(runs, firsts):
        tokens = traffic.train_rows(run.mix, run.seed, run.cfg["vocab_size"])
        t0 = time.perf_counter()
        reference = reference_call(run, tokens)
        run.log("sound", seed=run.seed, reference_s=time.perf_counter() - t0,
                call_s=first["seconds"],
                numbers=compare_numbers(run, first, reference))
        lower = None
        if run.seed in control_seeds:
            t0 = time.perf_counter()
            lower = reference_call(run, tokens,
                                   matmul=run.reference.int8_matmul)
            run.log("control", seed=run.seed,
                    control_s=time.perf_counter() - t0,
                    numbers=compare_numbers(run, lower, reference))
        run.log("norms", seed=run.seed, **{
            f"{side}_{key}": list(map(float, d[key]))
            for side, d in (("program", first), ("reference", reference),
                            ("control", lower)) if d
            for key in ("energy_norms", "change_norms")})


def compare(run, state) -> dict:
    import jax

    tokens, program = state["tokens"], state["first"]
    # free the program's state before the reference takes the device
    del state["trainer"], state["rows"]
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    reference = reference_call(run, tokens)
    out = compare_numbers(run, program, reference)
    run.log("compare", seconds=time.perf_counter() - t0, numbers=out)
    return {k: {"value": v["value"], "limit": v["limit"]}
            for k, v in out.items()}
