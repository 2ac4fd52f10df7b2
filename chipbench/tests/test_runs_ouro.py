"""The cell PR 36 added, ``train-ouro-seq4k``, at the rehearsal's size on the
CPU: the rehearsed run with its first steps; a ``null`` limit not held; the
int8 control and broken timed paths (a pass left out, half of every batch,
the gate's weights detached) coming out not correct; the four new readers on
a made trace and made counters; ``counts_ouro`` against the program's own
count of its operations."""

import io
import json
import os
import types
from contextlib import redirect_stdout

import numpy as np
import pytest

from chipbench import counts_ouro, ouro_reads, trace_reduce, traffic
from chipbench import run as run_mod
from chipbench.drivers import train_fit_first_steps, train_fit_mesh
from chipbench.drivers.train_fit_blockdiff import (first_steps_numbers,
                                                   reference_first_steps)
from chipbench.run import HERE, load_by_path

CELL = "train-ouro-seq4k"
FIRST_CALL = {"loss_epoch1", "loss_epoch2", "energy_worst_leaf",
              "change_worst_leaf", "change_median_leaf"}
FIRST_STEPS = {"first_loss_step1", "first_loss_step2",
               "first_moment_worst_leaf", "first_moment_median_leaf"}
NEW = ["mfu_loop.train", "loop_head_ms_per_step.train",
       "loop_pass_ms_per_step.train", "loop_exit_entropy.train"]


def bench():
    with open(os.path.join(run_mod.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config(name="ouro-2.6b-l6"):
    with open(os.path.join(run_mod.ROOT, "chipbench", "configs",
                           name + ".json")) as f:
        return json.load(f)


def rehearse(seed, trace=0, seconds=1.0):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run_mod.main(["--workload", CELL, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace),
                           "--rehearse"])
    assert rc == 0
    lines = [json.loads(l) for l in out.getvalue().strip().splitlines()]
    return lines[-1], lines[:-1]


def failed_numbers(result):
    return {k for k, c in result["compared"].items()
            if c["value"] > c["limit"]}


def test_rehearsed_run_ends_in_the_contracts_line():
    result, earlier = rehearse(3_000_000_019)
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(result["compared"]) == FIRST_CALL | FIRST_STEPS
    for c in result["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    split = next(l for l in earlier if l["line"] == "setup_split")
    assert split["jax_in_window"]["trace_s"] == 0
    assert split["jax_in_window"]["cache_misses"] == 0
    first = next(l for l in earlier if l["line"] == "first_call")
    assert set(first["model_metrics"]) == {"exit_mass", "loop_loss",
                                           "exit_entropy"}
    assert len(first["model_metrics"]["exit_mass"]) == 3    # the rehearsal's
    assert sum(first["model_metrics"]["exit_mass"]) == pytest.approx(1.0)
    assert len(first["first_steps_losses"]) == 2      # `iters` Adam steps
    gaps = next(l for l in earlier if l["line"] == "compare_first_steps")
    assert {"exit_gate/kernel", "exit_gate/bias"} <= set(gaps["gaps"])


def test_a_null_limit_prints_the_number_and_does_not_hold_it(monkeypatch):
    """As the cell's own mix has them: the numbers of the first call whose
    sound readings reach the int8 control's on the chip."""
    real = run_mod.traffic_mod.load

    def with_nulls(name):
        mix = real(name)
        mix["rehearse"]["limits"] = dict(
            mix["rehearse"]["limits"],
            **{k: v for k, v in mix["limits"].items() if v is None})
        return mix

    monkeypatch.setattr(run_mod.traffic_mod, "load", with_nulls)
    result, earlier = rehearse(8)
    nulls = {"loss_epoch1", "loss_epoch2", "energy_worst_leaf",
             "change_median_leaf", "first_loss_step2"}
    assert set(result["compared"]) == (FIRST_CALL | FIRST_STEPS) - nulls
    assert result["correct"] is True
    printed = next(l for l in earlier if l["line"] == "compare")["numbers"]
    printed.update(next(l for l in earlier
                        if l["line"] == "compare_first_steps")["numbers"])
    assert set(printed) == FIRST_CALL | FIRST_STEPS
    assert {k for k, c in printed.items() if c["limit"] is None} == nulls


def test_traced_rehearsal_reports_the_programs_counter():
    """A CPU has no device plane and no peak: of the cell's metrics the
    counters' are left, the new one among them."""
    result, _ = rehearse(7, trace=1)
    assert result["correct"] is True
    assert 0 < result["metrics"]["loop_exit_entropy.train"]["value"] <= 100
    assert result["metrics"]["retraces.train"]["value"] == 0
    assert not {"mfu_loop.train", "loop_head_ms_per_step.train",
                "loop_pass_ms_per_step.train"} & set(result["metrics"])


def test_the_gates_leaves_are_among_the_leaves_compared():
    run = run_mod.Run(bench(), CELL, 1, 2.0, False, True)
    names = run.load_reference().leaf_names(run.cfg)
    assert {"exit_gate/kernel", "exit_gate/bias", "block_0/ln1_post_scale",
            "block_1/down_kernel", "lm_head/kernel"} <= set(names)


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_int8_control_fails_the_training_comparison(seed):
    run = run_mod.Run(bench(), CELL, seed, 2.0, False, True)
    run.load_reference()
    tokens = traffic.train_rows(run.mix, seed, run.cfg["vocab_size"])
    int8 = run.reference.int8_matmul
    reference = train_fit_mesh.reference_call(run, tokens)
    control = train_fit_mesh.reference_call(run, tokens, matmul=int8)
    sound = train_fit_mesh.compare_numbers(run, reference, reference)
    assert all(c["value"] <= c["limit"] for c in sound.values())
    failed = [k for k, c in train_fit_mesh.compare_numbers(
        run, control, reference).items() if c["value"] > c["limit"]]
    assert failed, "the int8 control passed every number of the first call"
    # and the first steps: the control's gradients differ from the
    # reference's, the reference's own do not
    steps = reference_first_steps(run, tokens)
    low = reference_first_steps(run, tokens, matmul=int8)
    with redirect_stdout(io.StringIO()):
        sound = first_steps_numbers(run, steps, steps)
        lower = first_steps_numbers(run, low, steps)
    assert all(c["value"] == 0 for c in sound.values())
    assert {"first_moment_worst_leaf", "first_moment_median_leaf"} <= {
        k for k, c in lower.items() if c["value"] > c["limit"]}


def test_a_pass_left_out_is_not_correct(monkeypatch):
    """The program runs the stack one time fewer than the configuration
    says: the losses and every block's gradients are another model's."""
    real = train_fit_mesh.build_trainer

    def short(run):
        run.cfg = dict(run.cfg, registry_config=dict(
            run.cfg["registry_config"],
            passes=run.cfg["registry_config"]["passes"] - 1))
        return real(run)

    monkeypatch.setattr(train_fit_first_steps, "build_trainer", short)
    result, _ = rehearse(5)
    assert result["correct"] is False
    assert {"loss_epoch1", "energy_worst_leaf", "change_median_leaf",
            "first_loss_step1", "first_moment_worst_leaf",
            "first_moment_median_leaf"} <= failed_numbers(result)


def test_the_gates_weights_detached_is_not_correct(monkeypatch):
    """The gate's gradient exists only through the exit distribution: with
    its weights detached every other leaf's first moment is the reference's
    and the gate's is nothing."""
    import jax

    from sparkflow_tpu.models.looped_lm import LoopedLM

    real = LoopedLM._gate_logit
    monkeypatch.setattr(LoopedLM, "_gate_logit", lambda self, params, h: real(
        self, jax.lax.stop_gradient(params), h))
    result, earlier = rehearse(9)
    assert result["correct"] is False
    assert "first_moment_worst_leaf" in failed_numbers(result)
    gaps = next(l for l in earlier
                if l["line"] == "compare_first_steps")["gaps"]
    assert max(gaps, key=gaps.get) == "exit_gate/kernel"
    assert gaps["exit_gate/kernel"] > 0.3 > 100 * gaps["block_0/q_kernel"]


def test_a_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    """Half of every batch left out of the step's mean."""
    from sparkflow_tpu import core

    real = core._masked_mean
    monkeypatch.setattr(core, "_masked_mean", lambda lv, mask: real(
        lv, mask.at[mask.shape[0] // 2:].set(0.0)))
    result, _ = rehearse(6)
    assert result["correct"] is False
    assert {"loss_epoch1", "energy_worst_leaf", "first_loss_step1",
            "first_moment_worst_leaf",
            "first_moment_median_leaf"} <= failed_numbers(result)


# -- the counts against the program's own ---------------------------------------


def test_the_counts_at_the_cells_size():
    """ISSUE 36's reckoning: blocks 7.40 G, the four heads 2.42 G, causal
    attention 1.21 G a token, forward and backward."""
    parts = counts_ouro.forward_flops_per_token(config(), 4096)
    assert 3 * parts["blocks"] == pytest.approx(7.40e9, rel=2e-3)
    assert 3 * parts["heads"] == pytest.approx(2.42e9, rel=2e-3)
    assert 3 * parts["attention"] == pytest.approx(1.21e9, rel=2e-3)
    assert counts_ouro.train_flops_per_token(config(), 4096) == \
        pytest.approx(11.02e9, rel=1e-3)
    p = counts_ouro.projection_params(config())
    assert p["attention"] + p["mlp"] + 4 * 2048 == 51_388_416  # a layer


def _unrolled(step, carry, ts):
    """``jax.lax.scan`` as a Python loop: the compiler's count takes a
    loop's body once, whatever its trip count."""
    import jax
    import jax.numpy as jnp

    outs = []
    for t in range(len(ts)):
        carry, out = step(carry, t)
        outs.append(out)
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *outs)


@pytest.mark.parametrize("layers,passes,seq", [(2, 3, 64), (3, 4, 32)])
def test_train_flops_are_what_the_programs_cost_analysis_counts(
        layers, passes, seq, monkeypatch):
    """One row through the program without checkpoints (what a checkpoint
    makes again is no model work), the passes unrolled and the head in one
    stretch (no loop left): the compiler's own count of the gradient's
    operations is the counts', with every pair of the square in attention's
    place (at this length the program takes the ``jnp`` path, which computes
    and masks them) and, on top, the elementwise work the counts leave out."""
    import jax
    import jax.numpy as jnp

    from sparkflow_tpu.models import build_registry_spec, model_from_json

    cfg = dict(hidden_size=256, num_attention_heads=4, head_dim=64,
               intermediate_size=512, vocab_size=2048,
               num_hidden_layers=layers, total_ut_steps=passes)
    model = model_from_json(build_registry_spec(
        "looped_lm", vocab_size=2048, hidden=256, num_layers=layers,
        num_heads=4, head_dim=64, mlp_dim=512, passes=passes, max_len=seq,
        head_block=seq, remat=False))
    monkeypatch.setattr(jax.lax, "scan", _unrolled)
    grad = jax.jit(jax.grad(lambda p, ids: jnp.mean(
        model.loss_vector(p, {"input_ids": ids}))))
    counted = grad.lower(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((1, seq), jnp.int32)).cost_analysis()["flops"]
    parts = counts_ouro.forward_flops_per_token(cfg, seq)
    square = dict(parts, attention=parts["attention"] * seq * seq
                  / counts_ouro.causal_pairs(seq))
    assert 1.0 < counted / (3 * sum(square.values()) * seq) < 1.02
    assert counts_ouro.train_flops_per_token(cfg, seq) == \
        3 * sum(parts.values())


# -- the new readers -----------------------------------------------------------


def read(metric, run):
    reader = load_by_path(
        os.path.join(HERE, "layer_metrics", metric + ".py"),
        "chipbench_metric_" + metric.replace(".", "_"))
    return reader.read(run)


def made_run(scopes=True, counters=True, cfg=None):
    """Two traced calls of 10 s, 4 steps each, on one device; of each call's
    8 busy seconds 5 under ``loop_pass`` and 2 under ``loop_head``."""
    host = [(at, at + 10.0, "chipbench/fit_call") for at in (0.0, 10.0)]
    ops, modules = [], []
    for at in (0.0, 10.0):
        ops.append((at + 1.0, at + 9.0, "%while.1 = (s32[]) while(%t)"))
        modules.append((at + 1.0, at + 9.0, "jit_run(2)"))
    trace = {"devices": {0: {"ops": ops, "modules": modules}},
             "host": {"python3": host}}
    kept = {"devices": 1, "seconds": {"loop_pass": 10.0, "loop_head": 4.0,
                                      "attention": 6.0, "optimizer": 1.0}}
    metrics = {"exit_mass": [0.5, 0.25, 0.125, 0.125],
               "loop_loss": [10.8, 10.8, 10.8, 10.8],
               "exit_entropy": 1.2130075659799042}    # of that distribution
    return types.SimpleNamespace(
        trace_data=trace,
        reduced=trace_reduce.reduce(trace, window=(0.0, 20.0)),
        cfg=cfg or config(),
        device={"platform": "tpu", "kind": "TPU v5 lite"},
        cell={"chips": 1}, end_to_end={"train_tokens_per_s": 8192.0},
        counters={"fit_span": "chipbench/fit_call", "calls": 4,
                  "tokens": 4 * 4 * 8192, "tokens_per_step": 8192,
                  "seq_len": 4096, "counted_calls": [1, 2],
                  "call_seconds": [4.0, 4.0, 4.0, 4.0],
                  **({"scope_seconds": kept} if scopes else {}),
                  "model_metrics": metrics if counters else {}})


@pytest.mark.parametrize("metric, expected", [
    ("loop_pass_ms_per_step.train", 1250.0),
    ("loop_head_ms_per_step.train", 500.0),
    ("loop_exit_entropy.train", 87.5),      # 1.2130 of log 4 = 1.3863
    ("mfu_loop.train", 100 * 8192 * 11.022925824e9 / 197e12),
])
def test_new_readers_on_a_made_trace(metric, expected):
    value = read(metric, made_run())
    assert value == pytest.approx(expected)
    assert 0 < value and (not metric.startswith(("mfu", "loop_exit"))
                          or value < 100)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_scopes_and_counters_leaves_the_metric_out(
        metric):
    """The parent of this PR under these readers: nothing raised, nothing
    reported."""
    run = made_run(scopes=False, counters=False)
    if metric == "mfu_loop.train":
        del run.counters["counted_calls"]       # a driver that keeps none
    assert read(metric, run) is None


@pytest.mark.parametrize("metric", NEW)
def test_another_familys_run_leaves_the_metric_out(metric):
    run = made_run(cfg=config("sdar-30b-a3b-ep8"))
    assert ouro_reads.of_family(run) is False
    assert read(metric, run) is None


def test_a_rehearsal_on_a_cpu_has_no_peak_to_take_a_share_of():
    run = made_run()
    run.device = {"platform": "cpu", "kind": "cpu"}
    assert read("mfu_loop.train", run) is None
