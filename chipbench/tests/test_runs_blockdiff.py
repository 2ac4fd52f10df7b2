"""The cell PR 32 added, ``train-sdar-ep8-seq4k``, at the rehearsal's size on
the CPU: the rehearsed runs; a row counted as ``L`` tokens; the int8 control
and a broken timed path coming out not correct; ``counts_sdar`` against the
program's own count of visible pairs and parameters; the new readers on a
made trace."""

import io
import json
import os
import types
from contextlib import redirect_stdout

import numpy as np
import pytest

from chipbench import counts_sdar, sdar_reads, trace_reduce, traffic_blockdiff
from chipbench import run as run_mod
from chipbench.drivers import train_fit_blockdiff, train_fit_mesh
from chipbench.run import HERE, load_by_path

CELL = "train-sdar-ep8-seq4k"
NEW = ["block_attn_fwd_ms_per_step.train", "block_attn_bwd_ms_per_step.train",
       "block_attn_fwd_roofline.train", "block_attn_bwd_dq_roofline.train",
       "block_attn_bwd_dkv_roofline.train", "mfu_blockdiff.train",
       "masked_share.train"]


def bench():
    with open(os.path.join(run_mod.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def sdar_cfg():
    with open(os.path.join(run_mod.ROOT, "chipbench", "configs",
                           "sdar-30b-a3b-ep8.json")) as f:
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


def test_rehearsed_run_ends_in_the_contracts_line():
    result, earlier = rehearse(3_000_000_019)
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for c in result["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    split = next(l for l in earlier if l["line"] == "setup_split")
    assert split["jax_in_window"]["trace_s"] == 0
    assert split["jax_in_window"]["cache_misses"] == 0
    first = next(l for l in earlier if l["line"] == "first_call")
    assert set(first["model_metrics"]) == {"expert_load", "pairs_routed",
                                           "masked_tokens"}


def test_traced_rehearsal_reports_the_programs_counters():
    result, _ = rehearse(11, trace=1, seconds=0.0)
    assert result["attempted"] >= 3
    assert result["metrics"]["retraces.train"]["value"] == 0
    named = {m["name"] for m in bench()["per_layer"]
             if run_mod.applies(m, CELL)}
    assert set(NEW) <= named and set(result["metrics"]) <= named
    # device metrics need a chip; the program's counters do not
    assert not any("roofline" in k or k.startswith(("mfu", "block_attn"))
                   for k in result["metrics"])
    assert result["metrics"]["expert_load_max_over_mean.train"]["value"] >= 1
    assert 0 < result["metrics"]["pairs_here_share.train"]["value"] <= 100
    # 8 blocks a row of 32 tokens, 1 to 4 of 4 masked: between 25 and 100 %
    assert 25 <= result["metrics"]["masked_share.train"]["value"] <= 100


def made_state():
    with redirect_stdout(io.StringIO()):
        run = run_mod.Run(bench(), CELL, 13, 0.3, False, True)
        run.device = run_mod.device_block(1, True)
        run.load_reference()
        state = train_fit_blockdiff.setup(run)
    return run, state


def test_a_row_is_counted_as_its_tokens_not_its_positions():
    """A row of ``2 L`` positions is ``L`` tokens in the rate and in every
    counter the readers divide by."""
    run, state = made_state()
    with redirect_stdout(io.StringIO()):
        out = train_fit_blockdiff.window(run, state)
        train_fit_blockdiff.compare(run, state)
    c, mix = run.counters, run.mix
    length, rows = int(mix["seq_len"]), int(mix["rows"])
    assert state["tokens"].shape == (rows, 2 * length)
    assert c["seq_len"] == length
    assert c["tokens_per_step"] == mix["trainer"]["mini_batch_size"] * length
    assert c["positions_per_step"] == 2 * c["tokens_per_step"]
    assert c["tokens"] == c["calls"] * rows * length * mix["trainer"]["iters"]
    assert out["end_to_end"]["train_tokens_per_s"] == pytest.approx(
        c["tokens"] / c["elapsed_s"])
    # the readers' steps of a call: tokens of a call over tokens of a step
    assert c["tokens"] // c["calls"] // c["tokens_per_step"] == \
        state["steps_per_call"]
    assert c["model_metrics"]["pairs_routed"] == \
        c["positions_per_step"] * run.cfg["num_experts_per_tok"]


def test_a_null_limit_prints_the_number_and_does_not_hold_it(monkeypatch):
    """The cell's own mix holds the first sweep's loss and the leaves'
    change (PERF.md section 2); the rehearsal holds all five."""
    real = run_mod.Run(bench(), CELL, 1, 0.0, False, False).mix["limits"]
    assert {k for k, v in real.items() if v is not None} == {
        "loss_epoch1", "change", "first_loss_step1", "first_loss_step2",
        "first_moment", "first_moment_median", "first_blocks_logits"}
    numbers = {"loss_epoch1": {"value": 0.001, "limit": 0.03},
               "loss_epoch2": {"value": 0.2, "limit": None},
               "energy_worst_leaf": {"value": 6.4, "limit": None},
               "change_worst_leaf": {"value": 0.02, "limit": 0.15}}
    monkeypatch.setattr(train_fit_mesh, "compare", lambda run, state: numbers)
    monkeypatch.setattr(train_fit_blockdiff, "model_of", lambda run: run)
    monkeypatch.setattr(train_fit_blockdiff, "reference_first_steps",
                        lambda run, tokens: None)
    monkeypatch.setattr(
        train_fit_blockdiff, "first_steps_numbers", lambda run, a, b: {
            "first_moment_worst_leaf": {"value": 0.02, "limit": 0.1,
                                        "leaf": "block_0/router"}})
    monkeypatch.setattr(train_fit_blockdiff, "reference_first_blocks",
                        lambda run, tokens: None)
    monkeypatch.setattr(train_fit_blockdiff, "first_blocks_numbers",
                        lambda run, a, b: {})
    held = train_fit_blockdiff.compare(None, dict(
        first_steps=None, first_blocks=None, tokens=None))
    assert set(held) == {"loss_epoch1", "change_worst_leaf",
                         "first_moment_worst_leaf"}
    assert held["first_moment_worst_leaf"] == {"value": 0.02, "limit": 0.1}
    rehearsed = run_mod.Run(bench(), CELL, 1, 0.0, False, True).mix["limits"]
    assert all(v is not None for v in rehearsed.values())


def test_the_rows_are_the_seeds_and_every_seed_the_same_work():
    mix = dict(rows=4, seq_len=32, noise=dict(block_length=4),
               token_law=dict(law="zipf", exponent=1.0))
    cfg = dict(block_length=4, vocab_size=96, mask_token_id=700)
    a = traffic_blockdiff.noised_rows(mix, 3_000_000_019, cfg)
    assert a.shape == (4, 64) and a.dtype == np.int32
    np.testing.assert_array_equal(
        a, traffic_blockdiff.noised_rows(mix, 3_000_000_019, cfg))
    b = traffic_blockdiff.noised_rows(mix, 5, cfg)
    assert (a != b).any() and a.shape == b.shape
    for rows in (a, b):
        masked = rows[:, 32:] == 700
        np.testing.assert_array_equal(rows[:, 32:][~masked],
                                      rows[:, :32][~masked])
        k = masked.reshape(-1, 4).sum(axis=-1)
        assert k.min() >= 1 and k.max() <= 4 and rows[:, :32].max() < 96
    with pytest.raises(ValueError, match="blocks of 8"):
        traffic_blockdiff.noised_rows(mix, 1, dict(cfg, block_length=8))


def test_every_seed_trains_the_same_model_on_rows_of_its_own(monkeypatch):
    """The weights are the mix's (``weights_seed``), whatever ``--seed``: in
    set-up, in ``train_fit_mesh``'s comparison and in the first steps' and
    the first blocks' references. The rows are the seed's."""
    made = {}
    for seed in (5, 6):
        with redirect_stdout(io.StringIO()):
            run = run_mod.Run(bench(), CELL, seed, 0.3, False, True)
            run.device = run_mod.device_block(1, True)
            ref = run.load_reference()
            drawn, real = [], ref.init_params
            monkeypatch.setattr(ref, "init_params", lambda cfg, s: (
                drawn.append(s), real(cfg, s))[1])
            state = train_fit_blockdiff.setup(run)
            rows = state["tokens"]
            first = state["first"]["losses"]
            assert train_fit_blockdiff.compare(run, state)
        assert run.seed == seed and len(drawn) >= 4
        assert set(drawn) == {run.mix["weights_seed"]}
        made[seed] = (rows, first)
    assert (made[5][0] != made[6][0]).any()
    assert made[5][1] != made[6][1]


# -- the control and a broken timed path --------------------------------------


def failed_numbers(result):
    return {k for k, c in result["compared"].items()
            if c["value"] > c["limit"]}



@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_int8_control_fails_the_training_comparison(seed):
    run = run_mod.Run(bench(), CELL, seed, 2.0, False, True)
    run.load_reference()
    tokens = traffic_blockdiff.noised_rows(run.mix, seed, run.cfg)
    reference = train_fit_mesh.reference_call(run, tokens)
    control = train_fit_mesh.reference_call(
        run, tokens, matmul=run.reference.int8_matmul)
    sound = train_fit_mesh.compare_numbers(run, reference, reference)
    assert all(c["value"] <= c["limit"] for c in sound.values())
    failed = [k for k, c in train_fit_mesh.compare_numbers(
        run, control, reference).items() if c["value"] > c["limit"]]
    assert failed, "the int8 control passed every number of the comparison"
    # and the first steps: the control's gradients differ from the
    # reference's, the reference's own do not
    steps = train_fit_blockdiff.reference_first_steps(run, tokens)
    low = train_fit_blockdiff.reference_first_steps(
        run, tokens, matmul=run.reference.int8_matmul)
    with redirect_stdout(io.StringIO()):
        sound = train_fit_blockdiff.first_steps_numbers(run, steps, steps)
        lower = train_fit_blockdiff.first_steps_numbers(run, low, steps)
        blocks = train_fit_blockdiff.reference_first_blocks(run, tokens)
        lower.update(train_fit_blockdiff.first_blocks_numbers(
            run, train_fit_blockdiff.reference_first_blocks(
                run, tokens, matmul=run.reference.int8_matmul), blocks))
    assert all(c["value"] == 0 for c in sound.values())
    assert blocks.shape == (run.mix["first_blocks"] * run.cfg["block_length"],
                            run.cfg["vocab_size"])
    assert {"first_moment_worst_leaf", "first_moment_median_leaf",
            "first_blocks_logits"} <= {
        k for k, c in lower.items() if c["value"] > c["limit"]}


def test_a_noised_query_that_sees_its_own_blocks_clean_keys_is_not_correct(
        monkeypatch):
    """The fault this mask can have: a noised query also sees the clean
    copies of its own block (the answer it is asked for). The toy's
    comparison has to see it."""
    import jax.numpy as jnp
    from sparkflow_tpu.ops import block_attention as ba

    real = ba._rule_tile

    def leaky(qi, ki, block_q, block_k, length, block):
        p = qi * block_q + jnp.arange(block_q)[:, None]
        s = ki * block_k + jnp.arange(block_k)[None, :]
        own_clean = ((p >= length) & (s < length)
                     & ((p - length) // block == s // block))
        return real(qi, ki, block_q, block_k, length, block) | own_clean

    monkeypatch.setattr(ba, "_rule_tile", leaky)
    result, _ = rehearse(9)
    assert result["correct"] is False
    # the first steps see it in the gradients and the first blocks in the
    # logits, not only in a loss
    assert {"first_moment_worst_leaf", "first_moment_median_leaf",
            "first_blocks_logits"} <= failed_numbers(result)


def test_a_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    """Half of every batch left out of the step's mean: the first steps'
    gradients are another batch's."""
    from sparkflow_tpu import core

    real = core._masked_mean
    monkeypatch.setattr(core, "_masked_mean", lambda lv, mask: real(
        lv, mask.at[mask.shape[0] // 2:].set(0.0)))
    result, _ = rehearse(6)
    assert {"first_loss_step1", "first_moment_worst_leaf",
            "first_moment_median_leaf"} <= failed_numbers(result)


def test_the_first_steps_leave_one_set_of_weights_on_the_device():
    """Their weights and state go before the first call's program is loaded:
    with the seeded weights beside them it would not fit the chip."""
    run, state = made_state()
    assert len(state["first_steps"]["losses"]) == run.mix["trainer"]["iters"]
    assert state["fits"][0]["masked_tokens"].shape[:2] == (
        run.mix["trainer"]["iters"], 1)       # one batch, swept `iters` times
    moment = state["first_steps"]["moment"]
    assert isinstance(moment["lm_head"]["kernel"], np.ndarray)
    assert np.abs(moment["lm_head"]["kernel"]).max() > 0
    assert state["first_blocks"].shape == (
        run.mix["first_blocks"] * run.cfg["block_length"],
        run.cfg["vocab_size"])
    train_fit_mesh.release(state["trainer"])


# -- the counts against the program's own ---------------------------------------


@pytest.mark.parametrize("length,block", [(32, 4), (64, 8), (48, 4),
                                          (4096, 4)])
def test_visible_pairs_are_what_the_program_counts(length, block):
    from sparkflow_tpu.ops import block_attention as ba

    want = ba.visible_pairs(length, block)
    assert counts_sdar.visible_pairs(length, block) == want
    if length <= 64:
        assert int(np.asarray(ba.visible(length, block)).sum()) == want


def test_projection_counts_are_the_models_parameters():
    """``projection_params`` against the registered model's own shapes at
    the configuration's widths, and ISSUE 32's 456 M parameters."""
    from sparkflow_tpu.models import build_registry_spec, model_from_json

    cfg = sdar_cfg()
    model = model_from_json(build_registry_spec(
        cfg["registry_model"], **cfg["registry_config"]))
    specs = model.param_specs()
    block = {k: int(np.prod(shape)) for k, (shape, _) in
             specs["block_0"].items()}
    p = counts_sdar.projection_params(cfg)
    assert p["attention"] == sum(block[k] for k in (
        "q_kernel", "k_kernel", "v_kernel", "o_kernel"))
    assert p["router"] == block["router"]
    assert p["expert"] * 16 == sum(block[k] for k in (
        "experts_w1", "experts_w3", "experts_w2"))
    assert p["head"] == int(np.prod(specs["lm_head"]["kernel"][0]))
    assert specs["embed"]["tok"][0] == (18993, 2048)   # the mask token's row
    total = sum(int(np.prod(s)) for leaves in specs.values()
                for s, _ in leaves.values())
    assert 456e6 < total < 457e6
    # the reference makes the same tree
    run = run_mod.Run(bench(), CELL, 1, 0.0, False, False)
    shapes = run.load_reference().param_shapes(cfg)
    assert {g: {n: s for n, (s, _) in l.items()} for g, l in shapes.items()} \
        == {g: {n: tuple(s) for n, (s, _) in l.items()}
            for g, l in specs.items()}
    # forward, a token (two positions) a layer, in MFLOP: projections and
    # router 2 x 38.3, attention over the visible pairs 67.2, experts here
    # 9.4 a pair
    assert 2 * 2 * (p["attention"] + p["router"]) / 1e6 == pytest.approx(
        76.5, abs=0.1)
    assert 4 * 32 * 128 * counts_sdar.visible_pairs(4096, 4) / 4096 / 1e6 \
        == pytest.approx(67.2, abs=0.1)
    per_token = counts_sdar.train_flops_per_token(cfg, 4096, 2.0)
    assert 2.0e9 < per_token < 2.6e9


# -- the new readers -----------------------------------------------------------


def read(metric, run):
    reader = load_by_path(
        os.path.join(HERE, "layer_metrics", metric + ".py"),
        "chipbench_metric_" + metric.replace(".", "_"))
    return reader.read(run)


def made_run(kernels=True, cfg=None):
    """Two traced calls of 10 s, 4 steps each, on one device: a second of
    ``block_attn_fwd``, one and a half of ``block_attn_bwd_dq`` and two of
    ``block_attn_bwd_dkv`` a call."""
    host = [(at, at + 10.0, "chipbench/fit_call") for at in (0.0, 10.0)]
    ops, modules = [], []
    for at in (0.0, 10.0):
        ops.append((at + 1.0, at + 9.0, "%while.1 = (s32[]) while(%t)"))
        if kernels:
            ops += [(at + 1.0, at + 2.0, "%checkpoint_block_attn_fwd_.3 "
                     "= bf16[4]{0} custom-call(%q)"),
                    (at + 2.0, at + 3.5, "%transpose_block_attn_bwd_dq_"
                     ".1 = bf16[4]{0} custom-call(%g)"),
                    (at + 3.5, at + 5.5, "%transpose_block_attn_bwd_dkv"
                     "_.1 = bf16[4]{0} custom-call(%g)")]
        modules.append((at + 1.0, at + 9.0, "jit_run(2)"))
    trace = {"devices": {0: {"ops": ops, "modules": modules}},
             "host": {"python3": host}}
    return types.SimpleNamespace(
        trace_data=trace,
        reduced=trace_reduce.reduce(trace, window=(0.0, 20.0)),
        cfg=cfg or sdar_cfg(),
        device={"platform": "tpu", "kind": "TPU v5 lite"},
        cell={"chips": 1}, end_to_end={"train_tokens_per_s": 8192.0},
        counters={"fit_span": "chipbench/fit_call", "calls": 4,
                  "tokens": 4 * 4 * 8192, "tokens_per_step": 8192,
                  "seq_len": 4096, "counted_calls": [1, 2],
                  "call_seconds": [4.0, 4.0, 4.0, 4.0],
                  "model_metrics": {"expert_load": [[1024.0] * 16] * 4,
                                    "pairs_routed": 131072.0,
                                    "masked_tokens": 5120.0}})


@pytest.mark.parametrize("metric, expected", [
    ("block_attn_fwd_ms_per_step.train", 250.0),
    ("block_attn_bwd_ms_per_step.train", 875.0),
    ("masked_share.train", 62.5),
    ("pairs_here_share.train", 12.5),
    ("expert_load_max_over_mean.train", 1.0),
])
def test_new_readers_on_a_made_trace(metric, expected):
    assert read(metric, made_run()) == pytest.approx(expected)


def test_roofline_shares_are_the_counts_over_the_kernels_time():
    run = made_run()
    work = counts_sdar.kernel_work(run.cfg, 4096, 2)
    for kernel, seconds in (("block_attn_fwd", 0.25),
                            ("block_attn_bwd_dq", 0.375),
                            ("block_attn_bwd_dkv", 0.5)):
        share = read(kernel + "_roofline.train", run)
        assert share == pytest.approx(
            100 * work[kernel]["flops"] / 197e12 / seconds)
        assert 0 < share < 100
        # bound by operations, not by bytes
        assert work[kernel]["flops"] / 197e12 > work[kernel]["bytes"] / 819e9
    mfu = read("mfu_blockdiff.train", run)
    assert mfu == pytest.approx(
        100 * 8192 * counts_sdar.train_flops_per_token(
            run.cfg, 4096, 16 * 1024 / 8192) / 197e12)
    assert 0 < mfu < 100


@pytest.mark.parametrize("metric", NEW[:5])
def test_a_program_without_the_kernels_leaves_the_metric_out(metric):
    assert read(metric, made_run(kernels=False)) is None


@pytest.mark.parametrize("metric", NEW[2:])
def test_another_familys_run_leaves_the_metric_out(metric):
    """The parent of this PR, or the keye cell, read with these readers."""
    with open(os.path.join(run_mod.ROOT, "chipbench", "configs",
                           "keye-vl2-30b-a3b-ep8.json")) as f:
        run = made_run(cfg=json.load(f))
    del run.counters["model_metrics"]["masked_tokens"]
    assert read(metric, run) is None
    assert sdar_reads.of_family(run) is False
