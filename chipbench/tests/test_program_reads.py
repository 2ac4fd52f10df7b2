"""The readers of what the program itself names: its fit's host phases
(``train/...`` spans) and its kernels (``flash_fwd``, ``flash_bwd_``), on a
hand-made trace of two calls; and ``op_scopes`` on the recorded TPU trace."""

import os
import types

import pytest

from chipbench import op_scopes, trace_reduce
from chipbench.run import HERE, load_by_path

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def one_call(at, init_s, finish_s, tiny):
    """A traced call that starts at ``at``: the driver's span, the fit's
    phases inside it, ``tiny`` small programs while the state is built, then
    the fused program: a ``while`` that holds the three flash kernels and a
    fusion that only *reads* the forward kernel's result."""
    host = [(at, at + 10.0, "chipbench/fit_call"),
            (at + 0.5, at + 9.5, "train/fit"),
            (at + 0.5, at + 0.6, "train/plan"),
            (at + 0.6, at + 0.6 + init_s, "train/init_state"),
            (at + 1.85, at + 1.9, "train/transfer"),
            (at + 1.9, at + 2.0, "train/launch"),
            (at + 2.0, at + 9.0, "train/wait"),
            (at + 9.0, at + 9.0 + finish_s, "train/finish")]
    ops = [(at + 0.7 + 0.2 * i, at + 0.8 + 0.2 * i,
            f"%copy.{i} = f32[8]{{0}} copy(%p)") for i in range(tiny)]
    modules = [(s, e, "jit_copy(1)") for s, e, _ in ops]
    ops += [(at + 2.0, at + 9.0, "%while.1 = (s32[]) while(%tuple)"),
            (at + 2.0, at + 3.0,
             "%jvp_flash_fwd_.1 = bf16[4]{0} custom-call(%q)"),
            (at + 3.0, at + 5.0,
             "%transpose_jvp_flash_bwd_dq_.1 = bf16[4]{0} custom-call(%g)"),
            (at + 5.0, at + 6.0,
             "%transpose_jvp_flash_bwd_dkv_.1 = bf16[4]{0} custom-call(%g)"),
            (at + 6.0, at + 9.0,
             "%fusion.3 = bf16[4]{0} fusion(%jvp_flash_fwd_.1)")]
    modules.append((at + 2.0, at + 9.0, "jit_run(2)"))
    return host, ops, modules


def make_run(spans=True, kernels=True, traced=True):
    host, ops, modules = [], [], []
    for at, init_s, finish_s, tiny in ((0.0, 1.0, 0.4, 2), (10.0, 1.2, 0.2, 3)):
        h, o, m = one_call(at, init_s, finish_s, tiny)
        host += h if spans else h[:1]
        ops += o if kernels else [ev for ev in o if "flash" not in ev[2]]
        modules += m
    trace = {"devices": {0: {"ops": ops, "modules": modules}},
             "host": {"python3": host}}
    run = types.SimpleNamespace(
        trace_data=trace if traced else None,
        reduced=(trace_reduce.reduce(trace, window=(0.0, 20.0))
                 if traced else None),
        # four calls in the window, two of them traced; 64 steps a call
        counters={"fit_span": "chipbench/fit_call", "calls": 4,
                  "tokens": 4 * 64 * 4096, "tokens_per_step": 4096})
    return run


def read(metric, run):
    reader = load_by_path(
        os.path.join(HERE, "layer_metrics", metric + ".py"),
        "chipbench_metric_" + metric.replace(".", "_"))
    return reader.read(run)


@pytest.mark.parametrize("metric, expected", [
    # mean wall of train/init_state: 1.0 and 1.2 s
    ("fit_init_state_ms.train", 1100.0),
    # train/fit's start to train/wait's start is 1.5 s a call, the device
    # busy 0.2 and 0.3 s of it (the tiny programs)
    ("fit_prelaunch_idle_ms.train", 1250.0),
    # mean wall of train/finish: 0.4 and 0.2 s
    ("fit_finish_ms.train", 300.0),
    # 2 and 3 tiny programs, and the fused one
    ("programs_per_fit.train", 3.5),
    # 1 s a call of flash_fwd over 64 steps; the fusion that reads its
    # result names it among its operands and does not count
    ("flash_fwd_ms_per_step.train", 1e3 * 2.0 / 128),
    # dq 2 s and dkv 1 s a call
    ("flash_bwd_ms_per_step.train", 1e3 * 6.0 / 128),
])
def test_reader_on_a_hand_made_trace(metric, expected):
    assert read(metric, make_run()) == pytest.approx(expected)
    assert read(metric, make_run(traced=False)) is None


@pytest.mark.parametrize("metric", [
    "fit_init_state_ms.train", "fit_prelaunch_idle_ms.train",
    "fit_finish_ms.train"])
def test_a_program_without_the_spans_gives_nothing(metric):
    run = make_run(spans=False)
    assert read(metric, run) is None
    # what is read from the driver's own span is still there
    assert read("programs_per_fit.train", run) == pytest.approx(3.5)
    assert read("fit_host_ms.train", run) is not None


@pytest.mark.parametrize("metric", [
    "flash_fwd_ms_per_step.train", "flash_bwd_ms_per_step.train"])
def test_a_program_without_the_kernels_names_gives_nothing(metric):
    assert read(metric, make_run(kernels=False)) is None


def test_prelaunch_idle_and_finish_stay_inside_fit_host_ms():
    run = make_run()
    inside = (read("fit_prelaunch_idle_ms.train", run)
              + read("fit_finish_ms.train", run))
    assert inside <= read("fit_host_ms.train", run)


# -- op_scopes ---------------------------------------------------------------


def test_op_scopes_reads_the_recorded_trace():
    devices = op_scopes.read(os.path.join(DATA, "tiny_tpu.xplane.pb"))
    assert list(devices) == [0]
    module, ops = op_scopes.program_ops(devices, "jit_tiny")
    assert module == op_scopes.program_ops(devices)[0] == "jit_tiny"
    # leaves only: the while is a container and its body's ops stand for it
    names = {name for _, name, _ in ops}
    assert "convolution_tanh_fusion.2" in names and "while" not in names
    scopes = op_scopes.by_scope(ops, 3)
    assert scopes["jit(tiny)/while/body"] > 0
    assert sum(scopes.values()) == pytest.approx(sum(s for s, _, _ in ops))
    # the same leaves as the benchmark's own reduction of the same file
    # (which reads whole nanoseconds; this one picoseconds)
    trace = trace_reduce.read(os.path.join(DATA, "tiny_tpu.xplane.pb"))
    leaves = trace_reduce.leaf_events(trace["devices"][0]["ops"])
    assert len(ops) == len(leaves)
    assert sum(s for s, _, _ in ops) == pytest.approx(
        sum(e - s for s, e, _ in leaves), rel=1e-2)
    with pytest.raises(ValueError):
        op_scopes.program_ops(devices, "jit_absent")
    # no scope of the train step in this program: everything is "other"
    phases = op_scopes.by_phase(ops)
    assert phases["other"]["total"] == pytest.approx(sum(scopes.values()))
    top = op_scopes.top_ops(ops, 3)
    assert len(top["other"]) == 3 and not top["forward"]
    assert top["other"][0][1] >= top["other"][1][1] >= top["other"][2][1]


@pytest.mark.parametrize("path, expected", [
    ("jit(run)/while/body/loss/jvp(attention)/dot_general",
     ("forward", "attention")),
    ("jit(run)/while/body/loss/transpose(jvp(mlp))/mul", ("backward", "mlp")),
    ("jit(run)/loss/transpose(jvp(attention))/jvp(flash_bwd_dq)/pallas_call",
     ("backward", "attention")),
    ("jit(run)/loss/jvp(lm_head)/dot_general", ("forward", "lm_head")),
    ("jit(run)/loss/jvp()/reduce_sum", ("forward", "")),
    ("jit(run)/while/body/optimizer/add", ("optimizer", "")),
    ("jit(loss)/mul", ("other", "")),         # the program's name is no scope
    ("", ("other", "")),
])
def test_phase_of_a_path(path, expected):
    assert op_scopes.phase_of(path) == expected
