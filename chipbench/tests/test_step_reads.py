"""The readers PR 40 added over the program's list of a step's parts
(``chipbench/step_reads.py`` and eleven files of ``layer_metrics``): on a
hand-made ``run.counters["scope_seconds"]``, on a program without a part or
without the list (the parent of PR 40), on a run that keeps no scopes
(``train-gpt2m``'s), and on a traced rehearsal of each of the three drivers
whose mix sets ``trace_scopes``."""

import io
import json
import os
import types
from contextlib import redirect_stdout

import pytest

from chipbench import run as run_mod
from chipbench import step_reads
from chipbench.run import HERE, load_by_path

KEYE, SDAR, OURO = ("train-keye-vl2-ep8-seq8k", "train-sdar-ep8-seq4k",
                    "train-ouro-seq4k")
NEW = {
    "step_device_ms.train": [KEYE, SDAR, OURO],
    "unnamed_ms_per_step.train": [KEYE, SDAR, OURO],
    "fwd_ms_per_step.train": [KEYE, SDAR, OURO],
    "bwd_ms_per_step.train": [KEYE, SDAR, OURO],
    "remake_ms_per_step.train": [KEYE, SDAR, OURO],
    "opt_ms_per_step.train": [KEYE, SDAR, OURO],
    "attn_proj_ms_per_step.train": [KEYE, SDAR, OURO],
    "embed_ms_per_step.train": [KEYE, SDAR, OURO],
    "lm_head_ms_per_step.train": [KEYE, SDAR],
    "router_ms_per_step.train": [KEYE, SDAR],
    "mlp_ms_per_step.train": [OURO],
}
# the five that read a part of their own name; the other six read JAX's own
# path components, the scope ``optimizer`` and the root
PART_OF = {part + "_ms_per_step.train": part
           for part in ("attn_proj", "embed", "lm_head", "router", "mlp")}


def read(metric, run):
    reader = load_by_path(
        os.path.join(HERE, "layer_metrics", metric + ".py"),
        "chipbench_metric_" + metric.replace(".", "_"))
    return reader.read(run)


def made_run(drop=(), **more):
    """Two traced calls of 4 steps each on two devices (16 device-steps): a
    program of 16 s whose operations lie 15.2 s under parts; 4 s forward,
    11 s backward (3 of them the checkpoints' forward again), 0.8 s the
    optimizer, 0.2 s outside the three. ``experts`` holds ``expert_rows``
    and ``loss``, ``while``, ``dot_general`` are on the paths too: no part's
    time."""
    seconds = {"jit": 16.0, "run": 16.0, "while": 15.9, "dot_general": 9.0,
               "loss": 15.0, "jvp": 15.0, "transpose": 11.0,
               "rematted_computation": 3.0, "optimizer": 0.8,
               "embed": 0.16, "attn_proj": 4.0, "sparse_attention": 3.2,
               "indexer": 2.4, "router": 0.32, "experts": 1.6,
               "expert_rows": 0.4, "lm_head": 0.72, "batch": 0.0,
               "mlp": 2.0, **more}
    for name in drop:
        del seconds[name]
    trace = {"devices": {}, "host": {"python3": [
        (0.0, 10.0, "chipbench/fit_call"),
        (10.0, 20.0, "chipbench/fit_call")]}}
    return types.SimpleNamespace(
        trace_data=trace, reduced={}, cfg={}, device={"platform": "tpu"},
        cell={"chips": 1}, counters={
            "fit_span": "chipbench/fit_call", "calls": 4,
            "tokens": 4 * 4 * 16384, "tokens_per_step": 16384,
            "scope_seconds": {"devices": 2, "seconds": seconds}})


def test_the_program_has_the_list_and_the_readers_import_it():
    from sparkflow_tpu.utils.tracing import STEP_PARTS, STEP_SUBPARTS

    assert step_reads.step_parts() == tuple(STEP_PARTS)
    assert set(PART_OF.values()) <= set(STEP_PARTS)
    assert "expert_rows" in STEP_SUBPARTS and "expert_rows" not in STEP_PARTS


@pytest.mark.parametrize("metric", sorted(PART_OF))
def test_a_part_present_reads_its_seconds_a_device_and_step(metric):
    run = made_run()
    seconds = run.counters["scope_seconds"]["seconds"][PART_OF[metric]]
    assert read(metric, run) == pytest.approx(1e3 * seconds / 16)


@pytest.mark.parametrize("metric", sorted(PART_OF))
def test_a_part_absent_gives_none(metric):
    """The parent of PR 40 has no ``attn_proj``; ``looped_lm`` no
    ``lm_head`` or ``router``; the MoE families no ``mlp``."""
    assert read(metric, made_run(drop=[PART_OF[metric]])) is None


def test_the_steps_root_and_the_phases():
    run = made_run()
    assert read("step_device_ms.train", run) == pytest.approx(1000.0)
    # fwd = jvp less transpose: every backward operation is a transposed jvp
    assert read("fwd_ms_per_step.train", run) == pytest.approx(250.0)
    assert read("bwd_ms_per_step.train", run) == pytest.approx(687.5)
    assert read("remake_ms_per_step.train", run) == pytest.approx(187.5)
    assert read("opt_ms_per_step.train", run) == pytest.approx(50.0)
    # a program whose loss has no checkpoint and no backward pass
    bare = made_run(drop=["transpose", "rematted_computation"])
    assert read("fwd_ms_per_step.train", bare) == pytest.approx(937.5)
    assert read("bwd_ms_per_step.train", bare) is None
    assert read("remake_ms_per_step.train", bare) is None


def test_unnamed_is_the_root_less_the_parts():
    run = made_run()
    # 16 s less 15.2 s under parts: ``expert_rows`` (inside ``experts``),
    # ``loss`` and JAX's own names are no terms of the sum
    assert read("unnamed_ms_per_step.train", run) == pytest.approx(50.0)
    # a part the family does not have is a term of 0
    assert read("unnamed_ms_per_step.train",
                made_run(drop=["mlp", "indexer"])) == pytest.approx(325.0)
    # a part's name is a term whatever else is on the paths
    assert read("unnamed_ms_per_step.train",
                made_run(flash_attention=0.8)) == pytest.approx(0.0)


def test_a_program_without_the_list_gives_no_unnamed(monkeypatch):
    """The parent of PR 40: ``utils/tracing.py`` has no ``STEP_PARTS``."""
    from sparkflow_tpu.utils import tracing

    monkeypatch.delattr(tracing, "STEP_PARTS")
    run = made_run()
    assert step_reads.step_parts() is None
    assert read("unnamed_ms_per_step.train", run) is None
    # what reads JAX's own names and scopes the parent has still reads
    assert read("step_device_ms.train", run) == pytest.approx(1000.0)
    assert read("opt_ms_per_step.train", run) == pytest.approx(50.0)
    assert read("lm_head_ms_per_step.train", run) == pytest.approx(45.0)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_run_that_keeps_no_scopes_gives_none(metric):
    """``train-gpt2m``: its mix has no ``trace_scopes``; and any untraced
    run."""
    bare = made_run()
    del bare.counters["scope_seconds"]
    assert read(metric, bare) is None


def rehearse(cell):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run_mod.main(["--workload", cell, "--seed", "2147540013",
                           "--seconds", "0.0", "--trace", "1", "--rehearse"])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", [KEYE, SDAR, OURO, "train-gpt2m"])
def test_a_traced_rehearsal_still_ends_in_the_contracts_line(cell):
    result = rehearse(cell)
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    # a device's time needs a device: on a CPU the new metrics are left out
    assert not set(NEW) & set(result["metrics"])
    assert "retraces.train" in result["metrics"]


def test_benchmark_json_lists_each_readers_cells():
    with open(os.path.join(run_mod.ROOT, "BENCHMARK.json")) as f:
        named = {m["name"]: m for m in json.load(f)["per_layer"]}
    for metric, cells in NEW.items():
        entry = named[metric]
        assert entry["workloads"] == cells
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "ms", "lower", "device_trace")
        assert entry["layer"] == "Step builders and model"
        assert entry["moves"] == "train_tokens_per_s"
        assert not run_mod.applies(entry, "train-gpt2m")
        assert os.path.exists(os.path.join(HERE, "layer_metrics",
                                           metric + ".py"))
