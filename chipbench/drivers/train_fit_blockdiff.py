"""Driver ``train_fit_blockdiff``: ``train_fit_mesh``'s trainer, window,
counters and comparison for rows that carry their noise.

A training row of a block-diffusion mix is ``2 L`` positions, the ``L`` clean
ids and their noised copy (``chipbench/traffic_blockdiff.py``), and it is
``L`` tokens: what the user trained on. This driver imports what it can of
``train_fit_mesh`` and differs in six places:

- **the weights are the mix's, the rows the seed's** (:func:`model_of`). The
  mix gives ``weights_seed``, and every run draws the model from it;
  ``--seed`` makes the ids and the noise. A step's time follows the
  (position, expert) pairs that land on the held experts, and which experts
  an untrained router gives the mask token (a third of all positions, one
  token) is a draw of the weights: with the weights from ``--seed`` the seed
  chose how much work a run did, and the rate read the seed's router as much
  as the program (PERF.md section 6, PR 32). Every seed now trains the same
  model on rows of its own.
- ``setup`` (and ``readings``, for ``chipbench/control.py``) make the noised
  rows; the reference's ``train_steps`` takes them as they are.
- ``window`` counts a row as ``seq_len`` tokens: ``train_fit.window`` takes a
  row's width for its tokens, so ``train_tokens_per_s`` and the counters
  ``tokens``, ``tokens_per_step`` and ``seq_len`` are halved together (the
  readers' steps of a call divide one by the other); ``positions_per_step``
  keeps what the layers saw.
- ``compare`` holds the run to the numbers the mix gives a limit for; a
  ``null`` limit prints the number and does not hold it.
- **the first steps**, a second checked call (:func:`first_steps`). After the
  first call's 28 Adam steps from random weights bf16 and float32 stand
  apart on some seeds (PERF.md section 2), so of its five numbers two can be
  held, and those two hardly read a gradient's content. Before it, the same
  ``Trainer`` takes the mix's first batch alone from the seeded weights:
  ``iters`` steps on it. Compared with the reference's same steps: each
  step's loss, and per leaf the norm of the DIFFERENCE of Adam's first
  moments (the gradients as the optimizer got them, not their norms: half a
  batch turns a gradient before it changes its length).
- **the first blocks** (:func:`first_blocks`): a key too many in a query's
  view (the mask wrong by a block) turns a whole row's gradient by about a
  hundredth at 4096 tokens, less than bf16 does (PERF.md section 2), and a
  query's output by halves where a query sees few keys. So the program's
  logits (``Trainer.predict_fn``) at the first ``first_blocks`` blocks of
  the first row's noised copy, from the seeded weights, are held against the
  reference's: the median position's gap.

Traffic file: ``train_fit_mesh``'s keys, ``noise``, ``first_blocks``, and
under ``limits`` the first steps' ``first_loss_step<i>``, ``first_moment``,
``first_moment_median`` and the first blocks' ``first_blocks_logits``.
"""

from __future__ import annotations

import copy
import gc
import time

import numpy as np

from chipbench import traffic_blockdiff
from chipbench.drivers import train_fit_mesh as mesh
from chipbench.drivers.train_fit import (_trace_counts, batch_schedule,
                                         compare_numbers, program_first_call)

__all__ = ["setup", "window", "compare", "readings", "build_trainer"]

build_trainer = mesh.build_trainer


def model_of(run):
    """``run`` as whatever makes or follows the weights is to see it: its
    ``seed`` is the mix's ``weights_seed``, so the reference's
    ``init_params`` gives every run of the cell the same model, here and in
    ``train_fit_mesh``'s comparison. The rows stay ``run.seed``'s."""
    model = copy.copy(run)
    model.seed = int(run.mix["weights_seed"])
    return model


def _adam_first_moment(opt_state):
    """The first-moment tree inside an optax state (``ScaleByAdamState.mu``):
    the decayed sum of every gradient the optimizer got."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "mu") and hasattr(node, "nu"):
            return node.mu
        if isinstance(node, (tuple, list)):
            stack.extend(node)
    raise LookupError("the optimizer's state holds no Adam moments")


def first_steps(run, trainer, rows, p0) -> dict:
    """The program's side of the first steps: ``trainer.fit`` on the mix's
    first batch alone from the seeded weights (``iters`` sweeps of one step,
    a program of its own beside the window's). Each step's loss, and Adam's
    first moment after the last, on the host."""
    import jax

    res = trainer.fit(rows[:int(run.mix["trainer"]["mini_batch_size"])],
                      init_params=p0)
    out = dict(losses=[float(l) for l in res.losses],
               moment=jax.device_get(
                   _adam_first_moment(trainer._last_opt_state)))
    # these steps' weights and the seeded ones beside the first call's
    # program would pass the chip's memory
    trainer.params = trainer._last_opt_state = None
    return out


def reference_first_steps(run, tokens, matmul=None) -> dict:
    """The reference's side: the same steps on the same batch."""
    import jax

    ref, mix = run.reference, run.mix
    kw = {} if matmul is None else {"matmul": matmul}
    fresh = lambda: ref.init_params(run.cfg, run.seed)
    batch = tokens[:int(mix["trainer"]["mini_batch_size"])]
    out = ref.train_steps(
        fresh(), batch_schedule(mix, batch), run.cfg, again=fresh,
        learning_rate=float(mix["trainer"]["learning_rate"]),
        row_block=int(mix["reference_row_block"]),
        query_block=int(mix["reference_query_block"]), first_moment=True,
        **kw)
    return dict(losses=out["losses"],
                moment=jax.device_get(out.pop("first_moment")))


def first_steps_numbers(run, program: dict, reference: dict) -> dict:
    """Each step's loss, and the worst and the median leaf's gap between the
    two first moments: the norm of their difference over the larger of the
    reference's norm of that leaf and of the median leaf. Every leaf's gap
    goes into the ``compare_first_steps`` line."""
    import jax

    ref, limits = run.reference, run.mix["limits"]
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"first_loss_step{i + 1}"] = {
            "value": abs(a - b), "limit": limits[f"first_loss_step{i + 1}"],
            "program": a, "reference": b}
    norms = ref.leaf_norms(reference["moment"], run.cfg)
    gaps = ref.leaf_norms(jax.tree.map(
        np.subtract, program["moment"], reference["moment"]), run.cfg
    ) / np.maximum(norms, float(np.median(norms)))
    names, leaf = ref.leaf_names(run.cfg), int(np.argmax(gaps))
    out["first_moment_worst_leaf"] = {
        "value": float(gaps[leaf]), "limit": limits["first_moment"],
        "leaf": names[leaf]}
    out["first_moment_median_leaf"] = {
        "value": float(np.median(gaps)),
        "limit": limits["first_moment_median"]}
    run.log("compare_first_steps", numbers=out,
            gaps=dict(zip(names, map(float, gaps))))
    return out


def _first_positions(run) -> int:
    return int(run.mix["first_blocks"]) * int(run.cfg["block_length"])


def first_blocks(run, trainer, rows, p0) -> np.ndarray:
    """The program's side of the first blocks: the logits ``[positions,
    vocab held]`` of the first row's first noised positions, from the seeded
    weights."""
    logits = trainer.predict_fn("logits:0")(p0, rows[:1])
    return np.asarray(logits[0, :_first_positions(run)], np.float32)


def reference_first_blocks(run, tokens, matmul=None) -> np.ndarray:
    ref = run.reference
    kw = {} if matmul is None else {"matmul": matmul}
    return np.asarray(ref.noised_logits(
        ref.init_params(run.cfg, run.seed), tokens[0], run.cfg,
        _first_positions(run),
        query_block=int(run.mix["reference_query_block"]), **kw))


def first_blocks_numbers(run, program, reference) -> dict:
    """Every position's gap: the norm of the two logit rows' difference over
    the reference's norm. The median position's is held; one position whose
    eighth expert is another in bf16 moves the worst."""
    gaps = (np.linalg.norm(program - reference, axis=-1)
            / np.linalg.norm(reference, axis=-1))
    out = {"first_blocks_logits": {
        "value": float(np.median(gaps)),
        "limit": run.mix["limits"]["first_blocks_logits"],
        "worst": float(gaps.max()), "position": int(np.argmax(gaps))}}
    run.log("compare_first_blocks", numbers=out, gaps=gaps.tolist())
    return out


def compare(run, state) -> dict:
    """``train_fit_mesh``'s comparison of the first call, then the first
    steps' and the first blocks'. A number whose limit the mix gives as
    ``null`` is printed (the ``compare`` line has all five) and not held:
    where the program's sound readings pass the int8 control's, no limit
    lies between the two, and a limit above both would hold nothing
    (PERF.md section 2 says which numbers of ``blockdiff-seq4096`` and
    why)."""
    model = model_of(run)
    out = mesh.compare(model, state)    # frees the trainer first
    steps = first_steps_numbers(
        run, state["first_steps"], reference_first_steps(model,
                                                         state["tokens"]))
    steps.update(first_blocks_numbers(
        run, state["first_blocks"], reference_first_blocks(model,
                                                           state["tokens"])))
    out.update({k: {"value": v["value"], "limit": v["limit"]}
                for k, v in steps.items()})
    return {name: c for name, c in out.items() if c["limit"] is not None}


def setup(run) -> dict:
    import jax

    tokens = traffic_blockdiff.noised_rows(run.mix, run.seed, run.cfg)
    steps = len(batch_schedule(run.mix, tokens))
    rows = tokens.astype(np.float32)         # Trainer.fit's own feed type
    p0 = run.reference.init_params(run.cfg, model_of(run).seed)
    jax.block_until_ready(p0)
    run.phase("weights")

    trainer = build_trainer(run)
    fits = mesh.counted_fits(trainer)
    run.phase("trainer_build")

    blocks_made = first_blocks(run, trainer, rows, p0)
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
                first_steps=steps_made, first_blocks=blocks_made,
                steps_per_call=steps, fits=fits)


def window(run, state) -> dict:
    out = mesh.window(run, state)
    c = run.counters
    c["positions_per_step"] = c["tokens_per_step"]
    out["end_to_end"]["train_tokens_per_s"] /= 2
    for name in ("tokens", "tokens_per_step", "seq_len"):
        c[name] //= 2
    return out


def readings(runs, control_seeds) -> None:
    """For ``chipbench/control.py``: as ``train_fit_mesh.readings``, on the
    noised rows and with the first steps' numbers beside the first call's."""
    import jax

    trainer, made = build_trainer(runs[0]), []
    fits = mesh.counted_fits(trainer)
    rows_of = lambda run: traffic_blockdiff.noised_rows(run.mix, run.seed,
                                                        run.cfg)
    for run in runs:
        rows = rows_of(run).astype(np.float32)
        p0 = run.reference.init_params(run.cfg, model_of(run).seed)
        made.append((first_blocks(run, trainer, rows, p0),
                     first_steps(run, trainer, rows, p0),
                     program_first_call(run, trainer, rows, p0)))
        del p0
    mesh.release(trainer)
    del trainer
    gc.collect()
    jax.clear_caches()
    for run, (blocks, steps, first), metrics in zip(runs, made, fits[1::2]):
        tokens, model = rows_of(run), model_of(run)
        t0 = time.perf_counter()
        reference = mesh.reference_call(model, tokens)
        ref_steps = reference_first_steps(model, tokens)
        ref_blocks = reference_first_blocks(model, tokens)
        run.log("sound", seed=run.seed, reference_s=time.perf_counter() - t0,
                call_s=first["seconds"], losses=first["losses"],
                model_metrics=mesh.step_means([metrics]),
                numbers=dict(compare_numbers(run, first, reference),
                             **first_steps_numbers(run, steps, ref_steps),
                             **first_blocks_numbers(run, blocks, ref_blocks)))
        if run.seed in control_seeds:
            t0 = time.perf_counter()
            int8 = run.reference.int8_matmul
            lower = mesh.reference_call(model, tokens, matmul=int8)
            lower_steps = reference_first_steps(model, tokens, matmul=int8)
            lower_blocks = reference_first_blocks(model, tokens, matmul=int8)
            run.log("control", seed=run.seed,
                    control_s=time.perf_counter() - t0,
                    numbers=dict(
                        compare_numbers(run, lower, reference),
                        **first_steps_numbers(run, lower_steps, ref_steps),
                        **first_blocks_numbers(run, lower_blocks,
                                               ref_blocks)))
