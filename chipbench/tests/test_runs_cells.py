"""The rehearsed-run tests of ``test_runs.py`` as cases over every cell of
``BENCHMARK.json``; the readers PR 28 added on a hand-made trace and on the
recorded one; the counters of the traced calls and of no others; and
``counts_keye_vl2`` against the program's own counts at a toy size."""

import gc
import io
import json
import os
import types
import weakref
from contextlib import redirect_stdout

import numpy as np
import pytest

from chipbench import counts_keye_vl2, keye_reads, trace_reduce, traffic
from chipbench import run as run_mod
from chipbench.drivers import train_fit_mesh
from chipbench.run import HERE, load_by_path

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELLS = ["train-gpt2m", "train-keye-vl2-ep8-seq8k"]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench():
    with open(os.path.join(run_mod.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(cell, seed, trace=0, seconds=1.0):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run_mod.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace),
                           "--rehearse"])
    assert rc == 0
    lines = [json.loads(l) for l in out.getvalue().strip().splitlines()]
    return lines[-1], lines[:-1]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsed_run_ends_in_the_contracts_line(cell):
    result, earlier = rehearse(cell, 3_000_000_019)
    assert set(result) == RESULT_KEYS | {"compared"}
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"      # and so no result
    want = {m["name"] for m in bench()["end_to_end"]
            if run_mod.applies(m, cell)}
    assert set(result["metrics"]) == want == {"train_tokens_per_s", "setup_s"}
    for c in result["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    split = next(l for l in earlier if l["line"] == "setup_split")
    assert split["jax_in_window"]["trace_s"] == 0
    assert split["jax_in_window"]["cache_misses"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_the_counters(cell):
    result, earlier = rehearse(cell, 11, trace=1, seconds=0.0)
    assert set(result) == RESULT_KEYS | {"compared", "breakdown"}
    assert result["attempted"] >= 3
    assert result["metrics"]["retraces.train"]["value"] == 0
    named = {m["name"] for m in bench()["per_layer"]
             if run_mod.applies(m, cell)}
    assert set(result["metrics"]) <= named
    # device metrics need a chip; the program's counters do not
    assert not any("roofline" in k or k.startswith(("mfu", "device_idle"))
                   for k in result["metrics"])
    if cell == "train-keye-vl2-ep8-seq8k":
        assert result["metrics"]["expert_load_max_over_mean.train"][
            "value"] >= 1.0
        assert 0 < result["metrics"]["pairs_here_share.train"]["value"] <= 100


@pytest.mark.parametrize("trace", [True, False], ids=["traced", "plain"])
def test_the_counters_are_of_the_calls_the_metrics_time(trace):
    """A traced run's kernels and scopes are timed over the window's calls 1
    and 2, so the pairs routed here are counted over those calls' steps and
    not over the first call's, whose routing was another."""
    with redirect_stdout(io.StringIO()):
        run = run_mod.Run(bench(), CELLS[1], 13, 0.0 if trace else 0.5,
                          trace, True)
        run.device = run_mod.device_block(1, True)
        run.load_reference()
        state = train_fit_mesh.setup(run)
        gc.collect()
        gc.freeze()                 # as run.main does between the two
        try:
            train_fit_mesh.window(run, state)
            trainer = weakref.ref(state["trainer"])
            snapshot = dict(fits=list(state["fits"]),
                            counters=dict(run.counters))
            train_fit_mesh.compare(run, state)
        finally:
            gc.unfreeze()
    # the comparison's reference needs the chip's memory: the trainer has to
    # be gone by the count of its references, the collector being frozen
    assert trainer() is None
    assert snapshot["counters"]["model_metrics"] == \
        run.counters["model_metrics"]
    fits, calls = state["fits"], run.counters["counted_calls"]
    assert len(fits) == 1 + run.counters["calls"]      # the first call's too
    assert calls == ([1, 2] if trace else list(range(run.counters["calls"])))
    want = train_fit_mesh.step_means([fits[1 + i] for i in calls])
    assert run.counters["model_metrics"] == want
    assert np.shape(want["expert_load"]) == (run.cfg["num_hidden_layers"],
                                             run.cfg["num_experts"])
    assert train_fit_mesh.step_means(fits[:1])["expert_load"] != \
        want["expert_load"]
    if trace:
        rate = keye_reads.counted_rate(run)
        tokens = run.counters["tokens"] // run.counters["calls"]
        assert rate == pytest.approx(2 * tokens / sum(
            run.counters["call_seconds"][1:3]))


# -- the control: the reference in int8 has to fail --------------------------


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_int8_control_fails_the_training_comparison(seed):
    cell = "train-keye-vl2-ep8-seq8k"
    run = run_mod.Run(bench(), cell, seed, 2.0, False, True)
    run.load_reference()
    tokens = traffic.train_rows(run.mix, seed, run.cfg["vocab_size"])
    reference = train_fit_mesh.reference_call(run, tokens)
    control = train_fit_mesh.reference_call(
        run, tokens, matmul=run.reference.int8_matmul)
    sound = train_fit_mesh.compare_numbers(run, reference, reference)
    assert all(c["value"] <= c["limit"] for c in sound.values())
    failed = [k for k, c in train_fit_mesh.compare_numbers(
        run, control, reference).items() if c["value"] > c["limit"]]
    assert failed, "the int8 control passed every number of the comparison"


def test_a_dropped_expert_pair_is_not_correct(monkeypatch):
    """The fault a dropless layer can have: pairs past some capacity left
    out. The toy's comparison has to see it."""
    from sparkflow_tpu.ops import grouped_matmul as gm

    real = gm.group_rows

    def capped(experts, first, held, tile=gm.TILE):
        lay = real(experts, first, held, tile)
        n = experts.shape[0]
        return lay._replace(row_of_pair=lay.row_of_pair.at[n // 2:].set(
            lay.token_of_row.shape[0]))

    monkeypatch.setattr(gm, "group_rows", capped)
    result, _ = rehearse("train-keye-vl2-ep8-seq8k", 9)
    assert result["correct"] is False


# -- the new readers -----------------------------------------------------------


def read(metric, run):
    reader = load_by_path(
        os.path.join(HERE, "layer_metrics", metric + ".py"),
        "chipbench_metric_" + metric.replace(".", "_"))
    return reader.read(run)


def keye_cfg():
    with open(os.path.join(run_mod.ROOT, "chipbench", "configs",
                           "keye-vl2-30b-a3b-ep8.json")) as f:
        return json.load(f)


def made_run(kernels=True):
    """Two traced calls of 10 s, 4 steps each, on two devices: a second of
    ``sparse_attn_fwd``, two of the backward kernels and half a second of
    ``expert_gmm`` a call on each device."""
    devices, host = {}, []
    for at in (0.0, 10.0):
        host.append((at, at + 10.0, "chipbench/fit_call"))
    for dev in (0, 1):
        ops, modules = [], []
        for at in (0.0, 10.0):
            ops.append((at + 1.0, at + 9.0, "%while.1 = (s32[]) while(%t)"))
            if kernels:
                ops += [(at + 1.0, at + 2.0, "%checkpoint_sparse_attn_fwd_.3 "
                         "= bf16[4]{0} custom-call(%q)"),
                        (at + 2.0, at + 3.5, "%transpose_sparse_attn_bwd_dq_"
                         ".1 = bf16[4]{0} custom-call(%g)"),
                        (at + 3.5, at + 4.0, "%transpose_sparse_attn_bwd_dkv"
                         "_.1 = bf16[4]{0} custom-call(%g)"),
                        (at + 4.0, at + 4.5,
                         "%expert_gmm.7 = bf16[4]{0} custom-call(%x)")]
            ops.append((at + 7.0, at + 9.0,
                        "%fusion.3 = bf16[4]{0} fusion(%expert_gmm.7)"))
            modules.append((at + 1.0, at + 9.0, "jit_run(2)"))
        devices[dev] = {"ops": ops, "modules": modules}
    trace = {"devices": devices, "host": {"python3": host}}
    return types.SimpleNamespace(
        trace_data=trace,
        reduced=trace_reduce.reduce(trace, window=(0.0, 20.0)),
        cfg=keye_cfg(), device={"platform": "tpu", "kind": "TPU v5 lite"},
        cell={"chips": 1}, end_to_end={"train_tokens_per_s": 16384.0},
        counters={"fit_span": "chipbench/fit_call", "calls": 4,
                  "tokens": 4 * 4 * 16384, "tokens_per_step": 16384,
                  "seq_len": 8192, "counted_calls": [1, 2],
                  "call_seconds": [4.0, 4.0, 4.0, 4.0],
                  "model_metrics": {"expert_load": [[1024.0] * 16] * 4,
                                    "pairs_routed": 131072.0},
                  "scope_seconds": {"devices": 2, "seconds": {
                      "indexer": 3.2, "experts": 1.6}}})


@pytest.mark.parametrize("metric, expected", [
    ("sparse_attn_fwd_ms_per_step.train", 250.0),
    ("sparse_attn_bwd_ms_per_step.train", 500.0),
    # 3.2 s under the scope over 2 devices and 8 steps
    ("indexer_ms_per_step.train", 200.0),
    ("experts_ms_per_step.train", 100.0),
    ("expert_load_max_over_mean.train", 1.0),
    ("pairs_here_share.train", 12.5),
])
def test_new_readers_on_a_made_trace(metric, expected):
    assert read(metric, made_run()) == pytest.approx(expected)


def test_roofline_shares_are_the_counts_over_the_kernels_time():
    run = made_run()
    work = counts_keye_vl2.kernel_work(run.cfg, 8192, 2, 16384.0)
    fwd = read("sparse_attn_fwd_roofline.train", run)
    assert fwd == pytest.approx(
        100 * work["sparse_attn_fwd"]["flops"] / 197e12 / 0.25)
    assert 0 < fwd < 100
    assert 0 < read("expert_gmm_roofline.train", run) < 100
    assert 0 < read("mfu_active.train", run) < 100


@pytest.mark.parametrize("metric", [
    "sparse_attn_fwd_ms_per_step.train", "sparse_attn_fwd_roofline.train",
    "expert_tgmm_roofline.train", "sparse_attn_probs_roofline.train"])
def test_a_program_without_the_operation_leaves_the_metric_out(metric):
    assert read(metric, made_run(kernels=False)) is None


@pytest.mark.parametrize("metric", [
    "indexer_ms_per_step.train", "experts_ms_per_step.train",
    "mfu_active.train", "expert_load_max_over_mean.train",
    "pairs_here_share.train", "expert_gmm_roofline.train"])
def test_a_program_without_the_counters_leaves_the_metric_out(metric):
    run = made_run()
    del run.counters["model_metrics"], run.counters["scope_seconds"]
    assert read(metric, run) is None


def test_scope_seconds_of_the_recorded_trace():
    kept = train_fit_mesh.scope_seconds(os.path.join(DATA,
                                                     "tiny_tpu.xplane.pb"))
    assert kept["devices"] >= 1
    assert all(v > 0 for v in kept["seconds"].values())
    run = made_run()
    run.counters["scope_seconds"] = kept
    for scope in kept["seconds"]:
        assert keye_reads.scope_ms_per_step(run, scope) > 0
    assert keye_reads.scope_ms_per_step(run, "no-such-scope") is None


# -- the counts against the program's own ---------------------------------------


def test_selected_pairs_are_what_the_program_counts():
    """``selected_pairs`` against ``index_select``'s own mask."""
    import jax.numpy as jnp
    from sparkflow_tpu.ops import sparse_attention as sa

    r = np.random.default_rng(0)
    for seq, topk in ((32, 8), (32, 32), (16, 64)):
        qi = jnp.abs(jnp.asarray(r.normal(size=(1, seq, 2, 8)), jnp.float32))
        ki = jnp.abs(jnp.asarray(r.normal(size=(1, seq, 8)), jnp.float32))
        w = jnp.asarray(r.normal(size=(1, seq, 2)), jnp.float32)
        mask = sa.index_select(qi, ki, w, topk, block=8)
        assert int(mask.sum()) == counts_keye_vl2.selected_pairs(seq, topk)
    assert counts_keye_vl2.causal_pairs(32) == 32 * 33 // 2


def test_projection_counts_are_the_models_parameters():
    """``projection_params`` against the registered model's own shapes at
    the configuration's widths, and the per-token count against ISSUE 28's
    arithmetic (forward, a token a layer, in MFLOP: projections 38, indexer
    projections and scores 13, selected attention 29, experts here 9)."""
    from sparkflow_tpu.models import build_registry_spec, model_from_json

    cfg = keye_cfg()
    model = model_from_json(build_registry_spec(
        cfg["registry_model"], **cfg["registry_config"]))
    block = {k: int(np.prod(shape)) for k, (shape, _) in
             model.param_specs()["block_0"].items()}
    p = counts_keye_vl2.projection_params(cfg)
    assert p["attention"] == sum(block[k] for k in (
        "q_kernel", "k_kernel", "v_kernel", "o_kernel"))
    assert p["indexer"] == sum(block[k] for k in (
        "idx_q_kernel", "idx_k_kernel", "idx_w_kernel"))
    assert p["router"] == block["router"]
    assert p["expert"] * 16 == sum(block[k] for k in (
        "experts_w1", "experts_w3", "experts_w2"))
    assert p["head"] == int(np.prod(
        model.param_specs()["lm_head"]["kernel"][0]))
    total = sum(int(np.prod(s)) for leaves in model.param_specs().values()
                for s, _ in leaves.values())
    assert 464e6 < total < 466e6                       # ISSUE 28: 465 M
    sel = counts_keye_vl2.selected_pairs(8192, 2048) / 8192
    assert 2 * p["attention"] / 1e6 == pytest.approx(37.7, abs=0.1)
    assert 4 * 32 * 128 * sel / 1e6 == pytest.approx(29.4, abs=0.1)
    assert 2 * p["expert"] / 1e6 == pytest.approx(9.4, abs=0.1)
    per_token = counts_keye_vl2.train_flops_per_token(cfg, 8192, 1.0)
    assert 1.2e9 < per_token < 1.5e9
