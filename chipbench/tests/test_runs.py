"""Whole runs at toy sizes on the CPU (``--rehearse``): the last line has the
contract's keys, the lower-precision control fails the comparison, and a
timed path broken underneath comes out as not correct.

The toy configuration is float32 (``compute_dtype`` null), so the sound
program sits within float32 rounding of the reference; the toy's limits are
its own (the traffic file's ``rehearse`` block), the cell's are set from
bf16 runs at full size on the chip (PERF.md section 2).
"""

import io
import json
import os
from contextlib import redirect_stdout

import pytest

from chipbench import run as run_mod
from chipbench import traffic
from chipbench.drivers import train_fit

CELL = "train-gpt2m"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench():
    with open(os.path.join(run_mod.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(seed, trace=0, seconds=2.0):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run_mod.main(["--workload", CELL, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace),
                           "--rehearse"])
    assert rc == 0
    lines = [json.loads(l) for l in out.getvalue().strip().splitlines()]
    return lines[-1], lines[:-1]


def test_rehearsed_run_ends_in_the_contracts_line():
    result, earlier = rehearse(3_000_000_019)
    assert set(result) == RESULT_KEYS | {"compared"}
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"      # and so no result
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    want = {m["name"] for m in bench()["end_to_end"]
            if run_mod.applies(m, CELL)}
    assert set(result["metrics"]) == want
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in result["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    split = next(l for l in earlier if l["line"] == "setup_split")
    # the checked first call warmed everything the window drives
    assert split["jax_in_window"]["trace_s"] == 0
    assert split["jax_in_window"]["cache_misses"] == 0


def test_traced_rehearsal_reports_the_counters():
    result, _ = rehearse(11, trace=1, seconds=0.0)   # still holds the traced calls
    assert set(result) == RESULT_KEYS | {"compared", "breakdown"}
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert result["attempted"] >= 3
    assert result["metrics"]["retraces.train"]["value"] == 0
    assert not any(k.startswith(("device_idle", "mfu"))   # those need a chip
                   for k in result["metrics"])


def test_a_run_without_a_tpu_fails_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run_mod.main(["--workload", CELL, "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


# -- the control: the reference in int8 has to fail --------------------------


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_fails_the_training_comparison(seed):
    run = run_mod.Run(bench(), CELL, seed, 2.0, False, True)
    run.load_reference()
    tokens = traffic.train_rows(run.mix, seed, run.cfg["vocab_size"])
    reference = train_fit.reference_call(run, tokens)
    control = train_fit.reference_call(run, tokens,
                                       matmul=run.reference.int8_matmul)
    sound = train_fit.compare_numbers(run, reference, reference)
    assert all(c["value"] <= c["limit"] for c in sound.values())
    failed = [k for k, c in train_fit.compare_numbers(
        run, control, reference).items() if c["value"] > c["limit"]]
    assert failed, "the int8 control passed every number of the comparison"


# -- a timed path broken underneath comes out as not correct -----------------


def failed_numbers(result):
    assert result["correct"] is False
    return {k for k, c in result["compared"].items()
            if c["value"] > c["limit"]}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import optax

    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)
    result, _ = rehearse(5)
    assert {"change_worst_leaf", "change_median_leaf"} <= failed_numbers(result)


def test_a_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    from sparkflow_tpu import core

    real = core._masked_mean
    monkeypatch.setattr(core, "_masked_mean", lambda lv, mask: real(
        lv, mask.at[mask.shape[0] // 2:].set(0.0)))
    result, _ = rehearse(6)
    assert "loss_epoch1" in failed_numbers(result)


def test_a_call_that_trains_on_its_first_batches_only_is_not_correct(
        monkeypatch):
    """The fault the window's own many-batch program can have and a
    one-batch program cannot: the rows are cut into batches wrongly."""
    from sparkflow_tpu.trainer import Trainer

    real = Trainer.fit

    def fit(self, features, *args, **kwargs):
        return real(self, features[:len(features) // 2], *args, **kwargs)

    monkeypatch.setattr(Trainer, "fit", fit)
    result, _ = rehearse(7)
    assert failed_numbers(result)


def test_batches_taken_in_another_order_are_not_correct(monkeypatch):
    from sparkflow_tpu.trainer import Trainer

    real = Trainer.fit

    def fit(self, features, *args, **kwargs):
        return real(self, features[::-1].copy(), *args, **kwargs)

    monkeypatch.setattr(Trainer, "fit", fit)
    result, _ = rehearse(8)
    assert failed_numbers(result)
