"""The yardstick's own arithmetic: trace reduction, counts, peaks, traffic,
and that everything BENCHMARK.json names is found by name."""

import importlib
import json
import os

import numpy as np
import pytest

from chipbench import counts, trace_reduce, traffic
from chipbench.run import HERE, ROOT, applies

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- trace reduction ---------------------------------------------------------


def test_leaf_events_drop_containers():
    events = [(0.0, 10.0, "while"), (1.0, 2.0, "a"), (3.0, 6.0, "call"),
              (3.5, 4.0, "b"), (4.0, 5.0, "c"), (12.0, 13.0, "d")]
    assert sorted(n for _, _, n in trace_reduce.leaf_events(events)) == [
        "a", "b", "c", "d"]


def test_reduce_synthetic_trace():
    trace = {"devices": {0: {"ops": [(0.0, 10.0, "%while.1 = x while()"),
                                     (1.0, 3.0, "%fusion.1 = f32[] fusion()"),
                                     (5.0, 6.0, "%fusion.2 = f32[] fusion()")],
                             "modules": []}},
             "host": {"python": [(0.0, 10.0, "chipbench/fit_call"),
                                 (3.0, 5.0, "np.asarray(jax.Array)"),
                                 (6.2, 9.0, "PjitFunction(f)")]}}
    r = trace_reduce.reduce(trace, window=(0.0, 10.0))
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["idle_share"] == pytest.approx(0.7)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    gaps = dict(r["idle_gaps"])
    # every instant of a gap goes to the innermost span that covers it
    assert gaps["np.asarray_jax.Array_"] == pytest.approx(2.0)
    assert gaps["PjitFunction_f_"] == pytest.approx(2.8)
    assert gaps["chipbench/fit_call"] == pytest.approx(2.2)
    assert trace_reduce.busy_inside(r, (0.0, 4.0)) == pytest.approx(2.0)


def test_gaps_inside_a_running_program_are_the_devices_own():
    trace = {"devices": {0: {"ops": [(1.0, 3.0, "%a = f32[] fusion()"),
                                     (5.0, 6.0, "%b = f32[] fusion()"),
                                     (8.0, 9.0, "%c = f32[] fusion()")],
                             "modules": [(1.0, 6.0, "jit_f"),
                                         (8.0, 9.0, "jit_f")]}},
             "host": {"python": [(0.0, 10.0, "outer"), (6.0, 8.0, "copy")]}}
    gaps = dict(trace_reduce.reduce(trace, window=(0.0, 10.0))["idle_gaps"])
    assert gaps["_inside_a_running_program_"] == pytest.approx(2.0)
    assert gaps["copy"] == pytest.approx(2.0)
    assert gaps["outer"] == pytest.approx(2.0)


def test_reduce_recorded_tpu_trace():
    """A trace recorded on a TPU v5e (PR 24): two executions of a jitted scan
    of four matmuls, each under its own TraceAnnotation, 20 ms apart."""
    trace = trace_reduce.read(os.path.join(DATA, "tiny_tpu.xplane.pb"))
    assert list(trace["devices"]) == [0]
    assert len(trace["devices"][0]["modules"]) == 2
    a, b = (trace_reduce.host_spans(trace, f"chipbench/probe_{x}")[0]
            for x in "ab")
    r = trace_reduce.reduce(trace, window=(a[0], b[1]))
    # the while op contains its body: busy time is the leaves', and the
    # 20 ms sleep between the two calls is idle
    assert 0 < r["busy_s"] < 1e-3
    assert r["idle_share"] > 0.9
    assert r["window_s"] > 0.02
    names = [n for n, _ in r["device_ops"]]
    assert "convolution_tanh_fusion.2" in names and "while" not in names
    assert r["idle_gaps"][0][0] == "_time_sleep"
    assert r["idle_gaps"][0][1] == pytest.approx(0.02, rel=0.2)
    assert trace_reduce.busy_inside(r, a) > 0


def test_reduce_refuses_a_trace_without_a_device():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {}, "host": {}})


# -- counts and peaks --------------------------------------------------------


def test_counts_agree_with_the_programs_at_gpt2_medium():
    from sparkflow_tpu.utils import flops

    kw = dict(batch=8, seq=1024, hidden=1024, num_layers=24, mlp_dim=4096,
              vocab_size=50257, causal=True)
    assert counts.transformer_train_step_flops(**kw) == \
        flops.transformer_train_step_flops(**kw)
    assert counts.attention_flops(8, 16, 1024, 1024, 64, True, True) == \
        flops.attention_flops(8, 16, 1024, 1024, 64, True, True)
    cfg = json.load(open(os.path.join(HERE, "configs", "gpt2-medium.json")))
    assert counts.train_flops_per_token(cfg, 1024) * 8192 == pytest.approx(
        counts.transformer_train_step_flops(**kw))


def test_peaks_know_the_v5e_and_refuse_the_unknown():
    assert counts.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert counts.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v9", "cpu", "_source"):
        with pytest.raises(KeyError):
            counts.peaks_for(kind)


# -- traffic -----------------------------------------------------------------


def test_train_rows_come_from_the_seed():
    mix = traffic.load("pretrain-seq1024")
    a, b = traffic.train_rows(mix, 1, 50257), traffic.train_rows(mix, 2, 50257)
    assert a.shape == (128, 1024) and a.dtype == np.int32
    assert (a != b).any() and (a == traffic.train_rows(mix, 1, 50257)).all()
    assert len({row.tobytes() for row in a}) == 128
    assert a.min() >= 0 and a.max() < 50257
    # Zipf's law: low ids come most often, and half the tokens are among the
    # first few hundred ids; the file's law is what a run draws
    assert mix["token_law"] == {"law": "zipf", "exponent": 1.0}
    counts = np.bincount(a.ravel(), minlength=50257)
    assert counts.argmax() == 0 and counts[:300].sum() > a.size / 2
    uniform = traffic.train_rows(dict(mix, token_law={"law": "uniform"}), 1,
                                 50257)
    assert np.bincount(uniform.ravel(), minlength=50257)[:300].sum() < \
        a.size / 50
    with pytest.raises(ValueError):
        traffic.train_rows(dict(mix, token_law={"law": "poisson"}), 1, 50257)


def test_the_reference_follows_a_calls_batches_in_the_rows_own_order():
    from chipbench.drivers import train_fit

    mix = traffic.load("pretrain-seq1024")
    rows = traffic.train_rows(mix, 3_000_000_019, 50257)
    batches = train_fit.batch_schedule(mix, rows)
    assert batches.shape == (64, 4, 1024)
    assert (batches[:32].reshape(128, 1024) == rows).all()
    assert (batches[32:] == batches[:32]).all()
    shuffled = dict(mix, trainer=dict(mix["trainer"], shuffle_per_iter=True))
    with pytest.raises(ValueError):     # an order the program draws itself
        train_fit.batch_schedule(shuffled, rows)


# -- everything is found by name ---------------------------------------------


def test_everything_named_in_benchmark_json_is_found(bench):
    cfgs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for cell in bench["workloads"]:
        entry = cfgs[cell["config"]]
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == entry["source"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        assert os.path.exists(os.path.join(
            os.path.dirname(os.path.join(ROOT, entry["file"])),
            cfg["reference"]))
        mix = traffic.load(cell["traffic"])
        driver = importlib.import_module(f"chipbench.drivers.{mix['driver']}")
        assert all(hasattr(driver, f) for f in ("setup", "window", "compare"))
        assert any(applies(m, cell["name"]) and m["name"] != "setup_s"
                   for m in bench["end_to_end"])
        assert any(applies(m, cell["name"]) for m in bench["per_layer"])
    for metric in bench["per_layer"]:
        assert metric["moves"] in e2e
        path = os.path.join(HERE, "layer_metrics", metric["name"] + ".py")
        assert os.path.exists(path), path
        assert "def read(run)" in open(path).read()
