"""The two readers PR 34 added (``expert_rows_ms_per_step.train``,
``expert_rows_live_share.train``): on a hand-made run, on a run without the
scope or the counters (a parent's, ``train-gpt2m``'s), and on a traced
rehearsal of each MoE cell."""

import io
import json
import os
import types
from contextlib import redirect_stdout

import pytest

from chipbench import run as run_mod
from chipbench.run import HERE, load_by_path

NEW = ["expert_rows_ms_per_step.train", "expert_rows_live_share.train"]
MOE_CELLS = ["train-keye-vl2-ep8-seq8k", "train-sdar-ep8-seq4k"]


def read(metric, run):
    reader = load_by_path(
        os.path.join(HERE, "layer_metrics", metric + ".py"),
        "chipbench_metric_" + metric.replace(".", "_"))
    return reader.read(run)


def made_run(scope=True, counters=True):
    """Two traced calls of 4 steps each on two devices: 0.8 s under the scope
    ``expert_rows``; 4 layers whose buffers (2 rows of 69 632) have 13 056,
    26 112, 26 112 and 39 168 rows in use."""
    seconds = {"experts": 1.6}
    metrics = {"expert_load": [[1024.0] * 16] * 4, "pairs_routed": 131072.0}
    if scope:
        seconds["expert_rows"] = 0.8
    if counters:
        metrics.update(expert_rows_live=[13056.0, 26112.0, 26112.0, 39168.0],
                       expert_rows_bound=139264.0)
    trace = {"devices": {}, "host": {"python3": [
        (0.0, 10.0, "chipbench/fit_call"), (10.0, 20.0, "chipbench/fit_call")]}}
    return types.SimpleNamespace(
        trace_data=trace, reduced={}, cfg={}, device={"platform": "tpu"},
        cell={"chips": 1}, counters={
            "fit_span": "chipbench/fit_call", "calls": 4,
            "tokens": 4 * 4 * 16384, "tokens_per_step": 16384,
            "model_metrics": metrics,
            "scope_seconds": {"devices": 2, "seconds": seconds}})


def test_the_readers_on_a_made_run():
    run = made_run()
    # 0.8 s over 2 devices and 8 steps
    assert read(NEW[0], run) == pytest.approx(50.0)
    assert read(NEW[1], run) == pytest.approx(100 * 26112.0 / 139264.0)


@pytest.mark.parametrize("metric, kept", [(NEW[0], dict(scope=False)),
                                          (NEW[1], dict(counters=False))])
def test_a_program_without_the_scope_or_the_counters_gives_none(metric, kept):
    """The parent of PR 34, and any dense model's cell."""
    assert read(metric, made_run(**kept)) is None
    bare = made_run()
    del bare.counters["model_metrics"], bare.counters["scope_seconds"]
    assert read(metric, bare) is None


def rehearse(cell):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run_mod.main(["--workload", cell, "--seed", "13", "--seconds",
                           "0.0", "--trace", "1", "--rehearse"])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", MOE_CELLS)
def test_a_traced_rehearsal_of_a_moe_mix_reports_the_live_share(cell):
    metrics = rehearse(cell)["metrics"]
    # the toy's experts hold every pair or a part: never more than the bound
    assert 0 < metrics[NEW[1]]["value"] <= 100
    assert metrics[NEW[1]]["unit"] == "%"
    assert NEW[0] not in metrics            # a device's time needs a device


def test_the_dense_cell_reports_neither():
    with open(os.path.join(run_mod.ROOT, "BENCHMARK.json")) as f:
        named = {m["name"]: m for m in json.load(f)["per_layer"]}
    for metric in NEW:
        assert named[metric]["workloads"] == MOE_CELLS
        assert not run_mod.applies(named[metric], "train-gpt2m")
    assert not set(NEW) & set(rehearse("train-gpt2m")["metrics"])
