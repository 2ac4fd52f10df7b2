"""Driver ``train_fit_first_steps``: ``train_fit_mesh``'s trainer, window,
counters and comparison, and before the first call a second checked call,
**the first steps** (``train_fit_blockdiff``'s, imported as they are).

Why: after a first call's Adam steps from random weights bf16 and float32
can stand apart on some seeds (a looped model's exit gates take sides in
their first steps, and which side is a near-tie: PERF.md section 2), so of
the first call's five numbers those whose sound readings reach the int8
control's get a ``null`` limit and are printed, not held, and what is left
hardly reads a gradient's content. The first steps do: the same ``Trainer``
takes the mix's first batch alone from the seeded weights (``iters`` Adam
steps, a program of its own beside the window's), and after the window the
reference follows the same steps. Compared: each step's loss, and per leaf
the norm of the DIFFERENCE of Adam's first moments over the reference's norm
(the gradients as the optimizer got them, every leaf's: the exit gate's
among them).

Traffic file: ``train_fit_mesh``'s keys, and under ``limits`` the first
steps' ``first_loss_step<i>``, ``first_moment``, ``first_moment_median``.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import traffic
from chipbench.drivers import train_fit_mesh as mesh
from chipbench.drivers.train_fit import (_trace_counts, batch_schedule,
                                         compare_numbers, program_first_call)
from chipbench.drivers.train_fit_blockdiff import (first_steps,
                                                   first_steps_numbers,
                                                   reference_first_steps)

__all__ = ["setup", "window", "compare", "readings", "build_trainer"]

build_trainer = mesh.build_trainer
window = mesh.window


def setup(run) -> dict:
    import jax

    tokens = traffic.train_rows(run.mix, run.seed, run.cfg["vocab_size"])
    steps = len(batch_schedule(run.mix, tokens))
    rows = tokens.astype(np.float32)         # Trainer.fit's own feed type
    p0 = run.reference.init_params(run.cfg, run.seed)
    jax.block_until_ready(p0)
    run.phase("weights")

    trainer = build_trainer(run)
    fits = mesh.counted_fits(trainer)
    run.phase("trainer_build")

    steps_made = first_steps(run, trainer, rows, p0)
    run.phase("first_steps")
    first = program_first_call(run, trainer, rows, p0)
    del p0
    run.phase("first_call")
    run.log("first_call", seconds=first["seconds"],
            wall_time_s=first["wall_time_s"], losses=first["losses"],
            steps_per_call=steps, model_metrics=mesh.step_means(fits[-1:]),
            first_steps_losses=steps_made["losses"],
            traces=_trace_counts(trainer.recompile_report))
    return dict(trainer=trainer, rows=rows, tokens=tokens, first=first,
                first_steps=steps_made, steps_per_call=steps, fits=fits)


def compare(run, state) -> dict:
    """``train_fit_mesh``'s comparison of the first call, then the first
    steps'. A number whose limit the mix gives as ``null`` is printed (the
    ``compare`` line has all five) and not held: where the program's sound
    readings reach the int8 control's no limit lies between the two, and a
    limit above both would hold nothing."""
    out = mesh.compare(run, state)      # frees the trainer first
    steps = first_steps_numbers(
        run, state["first_steps"], reference_first_steps(run,
                                                         state["tokens"]))
    out.update({k: {"value": v["value"], "limit": v["limit"]}
                for k, v in steps.items()})
    return {name: c for name, c in out.items() if c["limit"] is not None}


def readings(runs, control_seeds) -> None:
    """For ``chipbench/control.py``: as ``train_fit_mesh.readings``, with the
    first steps' numbers beside the first call's. Every seed's first moment
    waits on the host for its reference (4 bytes a parameter: half a dozen
    seeds of a 510 M model, not a dozen, beside a machine's 40 GiB)."""
    import jax

    trainer, made = build_trainer(runs[0]), []
    fits = mesh.counted_fits(trainer)
    rows_of = lambda run: traffic.train_rows(run.mix, run.seed,
                                             run.cfg["vocab_size"])
    for run in runs:
        rows = rows_of(run).astype(np.float32)
        p0 = run.reference.init_params(run.cfg, run.seed)
        made.append((first_steps(run, trainer, rows, p0),
                     program_first_call(run, trainer, rows, p0)))
        del p0
    mesh.release(trainer)
    del trainer
    gc.collect()
    jax.clear_caches()
    for run, (steps, first), metrics in zip(runs, made, fits[1::2]):
        tokens = rows_of(run)
        t0 = time.perf_counter()
        reference = mesh.reference_call(run, tokens)
        ref_steps = reference_first_steps(run, tokens)
        run.log("sound", seed=run.seed, reference_s=time.perf_counter() - t0,
                call_s=first["seconds"], losses=first["losses"],
                model_metrics=mesh.step_means([metrics]),
                numbers=dict(compare_numbers(run, first, reference),
                             **first_steps_numbers(run, steps, ref_steps)))
        if run.seed in control_seeds:
            t0 = time.perf_counter()
            int8 = run.reference.int8_matmul
            lower = mesh.reference_call(run, tokens, matmul=int8)
            lower_steps = reference_first_steps(run, tokens, matmul=int8)
            run.log("control", seed=run.seed,
                    control_s=time.perf_counter() - t0,
                    numbers=dict(
                        compare_numbers(run, lower, reference),
                        **first_steps_numbers(run, lower_steps, ref_steps)))
