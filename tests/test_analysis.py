"""graftcheck (sparkflow_tpu.analysis): planted-defect detection per rule,
zero false positives on the repo's own code, and the serving/trainer
integrations.

Two invariants this file pins:

- every analyzer catches a deliberately planted defect and reports the
  documented rule id;
- the repo lints CLEAN under its own full pass (``python -m
  sparkflow_tpu.analysis sparkflow_tpu examples`` exits 0) — the static
  rules over every source file plus the jaxpr self-check over the model
  presets x the optimizer registry.
"""

import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparkflow_tpu.nn as nn
from sparkflow_tpu.analysis import (RecompileGuard, RULES, ast_lint, locks,
                                    track_recompiles)
from sparkflow_tpu.analysis.cli import main as cli_main, run_static
from sparkflow_tpu.analysis.findings import Finding, filter_suppressed
from sparkflow_tpu.analysis.jaxpr_lint import (lint_fn, lint_train_step,
                                               repo_self_check)
from sparkflow_tpu.graph_utils import build_graph
from sparkflow_tpu.models import model_from_json, presets
from sparkflow_tpu.optimizers import AVAILABLE_OPTIMIZERS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# jaxpr_lint: planted defects (GC-J1xx)
# ---------------------------------------------------------------------------


def test_j101_implicit_reshard_detected(dp_mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = NamedSharding(dp_mesh, P())

    def f(x):
        # declared P('dp') below, pinned replicated here -> GSPMD reshard
        return jax.lax.with_sharding_constraint(x, repl) * 2.0

    x = jax.ShapeDtypeStruct((8, 4), np.float32)
    fs = lint_fn(f, (x,), in_specs=(P("dp"),), mesh=dp_mesh)
    assert "GC-J101" in rules_of(fs)
    # aligned constraint: clean
    sharded = NamedSharding(dp_mesh, P("dp"))
    g = lambda x: jax.lax.with_sharding_constraint(x, sharded) * 2.0
    assert "GC-J101" not in rules_of(
        lint_fn(g, (x,), in_specs=(P("dp"),), mesh=dp_mesh))


def test_j102_large_replicated_detected(dp_mesh):
    from jax.sharding import PartitionSpec as P

    x = jax.ShapeDtypeStruct((1024, 512), np.float32)  # 2 MiB
    fs = lint_fn(lambda x: x.sum(), (x,), in_specs=(P(),), mesh=dp_mesh)
    assert "GC-J102" in rules_of(fs)
    # sharded placement of the same tensor: clean
    assert "GC-J102" not in rules_of(
        lint_fn(lambda x: x.sum(), (x,), in_specs=(P("dp"),), mesh=dp_mesh))


def test_j103_f64_promotion_detected():
    def f(x):
        return x * np.float64(1.5)  # strong double on the hot path

    x = jax.ShapeDtypeStruct((4, 4), np.float32)
    fs = lint_fn(f, (x,))
    assert "GC-J103" in rules_of(fs)
    # weak Python literals do NOT promote: clean
    assert "GC-J103" not in rules_of(lint_fn(lambda x: x * 1.5, (x,)))


def test_j104_weak_type_output_detected():
    x = jax.ShapeDtypeStruct((4,), np.float32)
    fs = lint_fn(lambda x: jnp.exp(2.0), (x,))  # scalar-dominated output
    assert "GC-J104" in rules_of(fs)
    assert "GC-J104" not in rules_of(lint_fn(lambda x: jnp.exp(x), (x,)))


def test_j105_missed_donation_detected():
    x = jax.ShapeDtypeStruct((1024, 512), np.float32)  # 2 MiB

    def f(x):
        return x * 2.0  # output aval == input aval

    assert "GC-J105" in rules_of(lint_fn(f, (x,)))
    # donated: clean
    assert "GC-J105" not in rules_of(lint_fn(f, (x,), donate_argnums=(0,)))
    # small tensors are never donation findings
    small = jax.ShapeDtypeStruct((4, 4), np.float32)
    assert "GC-J105" not in rules_of(lint_fn(f, (small,)))


def test_lint_train_step_runs_on_preset():
    mlp = model_from_json(presets.mlp(6, 3, hidden=(4,)))
    assert lint_train_step(mlp, "x:0", "y:0", "adam", batch=4) == []


def test_j108_full_pool_dequant_both_directions():
    """GC-J108 fires on a step that widens the ENTIRE quantized KV pool to
    float before gathering pages, stays quiet when the convert runs on the
    gathered pages only (the dequant-on-read idiom), and honors ignore."""
    from sparkflow_tpu.analysis.jaxpr_lint import lint_decode_collectives

    NUM_PAGES, page, h, d = 33, 8, 4, 8
    pool = jax.ShapeDtypeStruct((2, NUM_PAGES, page, h, d), jnp.int8)
    scales = jax.ShapeDtypeStruct((2, NUM_PAGES, h), np.float32)
    table = jax.ShapeDtypeStruct((4, 2), np.int32)

    def bad_step(kp, sc, t):
        # the planted defect: dequantize the whole pool, then gather
        full = kp.astype(jnp.float32) * sc[:, :, None, :, None]
        return full[0][t]

    found = lint_decode_collectives(bad_step, (pool, scales, table),
                                    kv_pool_pages=NUM_PAGES)
    assert any(f.rule == "GC-J108" for f in found), found
    f = next(f for f in found if f.rule == "GC-J108")
    assert f.detail["kv_pool_pages"] == NUM_PAGES

    def good_step(kp, sc, t):
        # dequant-on-read: convert only the gathered pages
        g = kp[0][t].astype(jnp.float32)
        return g * sc[0][t][:, :, None, :, None]

    assert lint_decode_collectives(good_step, (pool, scales, table),
                                   kv_pool_pages=NUM_PAGES) == []
    # without a quantized pool declared, the scan is off entirely
    assert lint_decode_collectives(bad_step, (pool, scales, table)) == []
    # and the ignore escape hatch silences it
    assert lint_decode_collectives(bad_step, (pool, scales, table),
                                   kv_pool_pages=NUM_PAGES,
                                   ignore=("GC-J108",)) == []


def test_j108_quantized_engine_repo_clean():
    """The repo's own int8 decode step never materializes the float pool:
    lint_decode_step wires kv_pool_pages automatically for a quantized
    engine and must come back empty."""
    from sparkflow_tpu.models.registry import (build_registry_spec,
                                               model_from_json as _mfj)
    from sparkflow_tpu.serving import DecodeEngine
    from sparkflow_tpu.analysis.jaxpr_lint import lint_decode_step

    spec = build_registry_spec("transformer_lm", vocab_size=61, hidden=32,
                               num_layers=2, num_heads=4, mlp_dim=64,
                               max_len=32, dropout=0.0)
    m = _mfj(spec)
    eng = DecodeEngine(m, m.init(jax.random.PRNGKey(0)), num_slots=4,
                       page_size=8, seed=0, kv_quant="int8", warmup=False)
    assert lint_decode_step(eng) == []


# ---------------------------------------------------------------------------
# ast_lint: planted defects (GC-A2xx)
# ---------------------------------------------------------------------------


def test_a201_host_sync_in_jit_detected():
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def step(x):
            v = float(x)          # concretizes a tracer
            print(x)              # trace-time print
            return x.item() + v   # host sync
    """)
    fs = [f for f in ast_lint.lint_source(src) if f.rule == "GC-A201"]
    assert len(fs) == 3
    assert all("step" in f.message for f in fs)


def test_a201_np_asarray_on_traced_arg():
    src = textwrap.dedent("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return np.asarray(x) + 1
    """)
    assert "GC-A201" in rules_of(ast_lint.lint_source(src))


def test_a202_traced_branch_detected():
    src = textwrap.dedent("""
        import jax

        def step(x):
            if x > 0:
                return x
            return -x

        fast = jax.jit(step)
    """)
    fs = [f for f in ast_lint.lint_source(src) if f.rule == "GC-A202"]
    assert len(fs) == 1 and "'x'" in fs[0].message


def test_a202_static_checks_exempt():
    # is-None / isinstance / hasattr / len / .shape tests are all static
    # under jit: branching on them is fine and must not be flagged
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def step(x, mask=None):
            if mask is None:
                return x
            if isinstance(x, tuple):
                x = x[0]
            if hasattr(x, "nope"):
                return x
            if x.ndim == 2 and x.shape[0] > 4 and len(x) > 2:
                return x * mask
            return x + mask
    """)
    assert "GC-A202" not in rules_of(ast_lint.lint_source(src))


def test_a202_tree_map_callback_not_traced():
    # jax.tree.map is not a tracing transform: branching inside its
    # callback on a (typically static-leaf) argument is not a finding
    src = textwrap.dedent("""
        import jax

        def pick(spec):
            if spec == "big":
                return 1
            return 0

        out = jax.tree.map(pick, {"a": "big"})
    """)
    assert ast_lint.lint_source(src) == []


def test_local_assignment_shadows_method_name():
    # the serving-engine pattern: a method jits a LOCAL callable that
    # shares the name of a host-side method; the method is not traced
    src = textwrap.dedent("""
        import jax

        class Engine:
            def predict(self, x):
                return float(x)  # host-side: allowed

            def _compile(self):
                predict = self._apply_fn()
                return jax.jit(predict)
    """)
    assert ast_lint.lint_source(src) == []


def test_a203_prng_key_reuse_detected():
    src = textwrap.dedent("""
        import jax

        def sample(key):
            a = jax.random.normal(key, (4,))
            b = jax.random.uniform(key, (4,))
            return a + b
    """)
    fs = [f for f in ast_lint.lint_source(src) if f.rule == "GC-A203"]
    assert len(fs) == 1 and "'key'" in fs[0].message


def test_a203_split_and_rebind_clean():
    src = textwrap.dedent("""
        import jax

        def sample(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, (4,))
            b = jax.random.uniform(k2, (4,))
            key = jax.random.fold_in(key, 7)
            c = jax.random.normal(key, (4,))
            return a + b + c
    """)
    assert "GC-A203" not in rules_of(ast_lint.lint_source(src))


def test_a203_exclusive_branches_clean_loop_reuse_caught():
    clean = textwrap.dedent("""
        import jax

        def sample(key, flag):
            if flag:
                return jax.random.normal(key, (4,))
            return jax.random.uniform(key, (4,))
    """)
    assert "GC-A203" not in rules_of(ast_lint.lint_source(clean))
    loop = textwrap.dedent("""
        import jax

        def sample(key, n):
            out = []
            for _ in range(n):
                out.append(jax.random.normal(key, (4,)))
            return out
    """)
    assert "GC-A203" in rules_of(ast_lint.lint_source(loop))


def test_a204_unhashable_static_default_detected():
    src = textwrap.dedent("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnums=(1,))
        def apply(x, dims=[1, 2]):
            return x.reshape(dims)
    """)
    fs = [f for f in ast_lint.lint_source(src) if f.rule == "GC-A204"]
    assert len(fs) == 1 and "'dims'" in fs[0].message
    # tuple default: hashable, clean
    ok = src.replace("[1, 2]", "(1, 2)")
    assert "GC-A204" not in rules_of(ast_lint.lint_source(ok))


# ---------------------------------------------------------------------------
# locks: planted defects (GC-L3xx)
# ---------------------------------------------------------------------------

_LOCKED_CLASS = textwrap.dedent("""
    import threading

    class Stats:
        def __init__(self):
            self._lock = threading.Lock()
            self.n = 0
            self.hits = 0

        def add(self, v):
            with self._lock:
                self.n += 1

        def race(self, v):
            self.n = 0          # guarded attr written without the lock
            self.hits += v      # rmw on unguarded shared state
""")


def test_l301_l302_detected():
    fs = locks.lint_source(_LOCKED_CLASS)
    assert rules_of(fs) == {"GC-L301", "GC-L302"}
    by_rule = {f.rule: f for f in fs}
    assert "self.n" in by_rule["GC-L301"].message
    assert "self.hits" in by_rule["GC-L302"].message


def test_locked_suffix_helper_convention():
    # a *_locked helper's body scans as lock-held (no L301/L302 inside it);
    # the enforcement moves to call sites: locked call clean, unlocked call
    # flagged as GC-L303
    src = textwrap.dedent("""
        import threading

        class Store:
            def __init__(self):
                self._lock = threading.Lock()
                self.evictions = 0

            def _sweep_locked(self):
                self.evictions += 1   # fine: caller holds the lock

            def tick(self):
                with self._lock:
                    self._sweep_locked()

            def broken(self):
                self._sweep_locked()  # GC-L303: no lock held
    """)
    fs = locks.lint_source(src)
    assert rules_of(fs) == {"GC-L303"}
    (f,) = fs
    assert "broken" in f.message and "_sweep_locked" in f.message


def test_lock_free_class_and_init_exempt():
    # no lock attribute -> the class never opted into the rules; and
    # __init__ writes are exempt even in lock-owning classes
    src = textwrap.dedent("""
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
    """)
    assert locks.lint_source(src) == []
    assert not any(f.line <= 8 for f in locks.lint_source(_LOCKED_CLASS))


# ---------------------------------------------------------------------------
# runtime guards (GC-R401)
# ---------------------------------------------------------------------------


def test_recompile_guard_counts_and_names_cause():
    guard = RecompileGuard(lambda x: x * 2, name="double")
    guard(jnp.ones((4,)))
    guard(jnp.ones((4,)))        # cache hit: no new trace
    assert guard.traces == 1 and guard.retraces == 0
    assert guard.findings() == []
    guard(jnp.ones((8,)))        # shape change: retrace
    guard(jnp.ones((8,), jnp.int32))  # dtype change: retrace
    assert guard.traces == 3
    fs = guard.findings()
    assert rules_of(fs) == {"GC-R401"}
    causes = "\n".join(guard.causes)
    assert "[4]" in causes and "[8]" in causes and "int32" in causes


def test_recompile_guard_wrap_and_mark_steady():
    guard = RecompileGuard(name="aot")
    fn = jax.jit(guard.wrap(lambda x: x + 1))
    fn(jnp.ones((2,)))
    guard.mark_steady()
    assert guard.steady_traces == 0 and guard.findings() == []
    fn(jnp.ones((3,)))           # post-steady trace: a regression
    assert guard.steady_traces == 1
    assert "GC-R401" in rules_of(guard.findings())


def test_track_recompiles_sees_core_train_step():
    from sparkflow_tpu import core
    from sparkflow_tpu.optimizers import build_optimizer

    model = model_from_json(presets.mlp(4, 2, hidden=(3,)))
    loss_fn = core.make_loss_fn(model, "x:0", "y:0")
    opt = build_optimizer("gradient_descent", 0.1)
    params = model.init(jax.random.PRNGKey(0))
    state = opt.init(params)
    rng = jax.random.PRNGKey(1)

    def batch(n):
        return (jnp.zeros((n, 4)), jnp.zeros((n, 2)), jnp.ones((n,)))

    with track_recompiles() as tracker:
        # params/opt state are donated by the step: re-thread them
        step = core.make_train_step(loss_fn, opt)
        x, y, m = batch(8)
        params, state, _ = step(params, state, x, y, m, rng)
        x, y, m = batch(8)
        params, state, _ = step(params, state, x, y, m, rng)  # cache hit
        assert tracker.traces == {"train_step": 1}
        x, y, m = batch(16)
        params, state, _ = step(params, state, x, y, m, rng)  # ragged batch
    assert tracker.traces["train_step"] == 2
    fs = tracker.findings()
    assert rules_of(fs) == {"GC-R401"}
    assert "16" in tracker.report()


def test_trainer_debug_recompiles_populates_report():
    from sparkflow_tpu.trainer import Trainer

    tr = Trainer(presets.mlp(4, 2, hidden=(3,)), "x:0", "y:0", iters=2,
                 mini_batch_size=8, debug_recompiles=True)
    rs = np.random.RandomState(0)
    tr.fit(rs.rand(16, 4).astype(np.float32),
           np.eye(2, dtype=np.float32)[rs.randint(0, 2, 16)])
    assert tr.recompile_report is not None
    assert "trace" in tr.recompile_report
    # a fixed-shape fit compiles each program once: no findings
    assert tr.recompile_findings == []


# ---------------------------------------------------------------------------
# serving integration: AOT ladder serves every size with zero retraces
# ---------------------------------------------------------------------------


def _serving_graph():
    def g():
        x = nn.placeholder([None, 4], name="x")
        h = nn.dense(x, 3, activation="relu")
        out = nn.dense(h, 2, name="out")
        nn.mean_squared_error(x, out)
    return build_graph(g)


def test_engine_zero_retraces_after_warmup():
    from sparkflow_tpu.serving import InferenceEngine

    rs = np.random.RandomState(0)
    weights = [rs.randn(4, 3).astype(np.float32),
               rs.randn(3).astype(np.float32),
               rs.randn(3, 2).astype(np.float32),
               rs.randn(2).astype(np.float32)]
    eng = InferenceEngine(_serving_graph(), weights, input_name="x:0",
                          output_name="out/BiasAdd:0", max_batch=8)
    stats = eng.stats()
    # warmup compiled exactly the ladder, one guard trace per bucket
    assert stats["traces"] == stats["aot_compiles"] == len(eng.buckets)
    assert stats["steady_traces"] == 0
    # every request size 1..max_batch (plus a chunked oversize request)
    # serves from the compiled ladder: no new traces, no fallback compiles
    for n in list(range(1, 9)) + [11]:
        out = eng.predict(rs.randn(n, 4).astype(np.float32))
        assert out.shape == (n, 2)
    stats = eng.stats()
    assert stats["steady_traces"] == 0
    assert stats["fallback_compiles"] == 0
    assert stats["requests"] == 9 and stats["rows"] == sum(range(1, 9)) + 11
    assert eng.recompile_guard.findings() == []


# ---------------------------------------------------------------------------
# dtype stability (satellite): presets x optimizer registry stay f32-pure
# ---------------------------------------------------------------------------


def test_optimizer_registry_dtype_stable():
    """No registry optimizer may introduce f64 (even latently, under an
    x64 flip) or weakly-typed outputs into the train step."""
    mlp = model_from_json(presets.mlp(6, 3, hidden=(4,)))
    for opt in AVAILABLE_OPTIMIZERS:
        fs = lint_train_step(mlp, "x:0", "y:0", opt, batch=4)
        bad = [f for f in fs if f.rule in ("GC-J103", "GC-J104")]
        assert not bad, f"{opt}: {[f.render() for f in bad]}"


# ---------------------------------------------------------------------------
# the compile-cache helper under the linters (no false positives)
# ---------------------------------------------------------------------------


def test_hw_helper_clean_under_static_pass():
    path = os.path.join(REPO, "sparkflow_tpu", "utils", "hw.py")
    assert ast_lint.lint_file(path) == []
    assert locks.lint_file(path) == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


def test_trailing_suppression_drops_finding():
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def step(x):
            if x > 0:  # graftcheck: disable=GC-A202
                return x
            return -x
    """)
    assert ast_lint.lint_source(src) == []
    # wrong rule id on the comment: the finding survives
    other = src.replace("GC-A202", "GC-A201")
    assert "GC-A202" in rules_of(ast_lint.lint_source(other))


def test_file_wide_suppression_only_in_header():
    body = textwrap.dedent("""
        import jax

        @jax.jit
        def step(x):
            if x > 0:
                return x
            return -x
    """)
    header = "# graftcheck: disable-file=GC-A202\n"
    assert ast_lint.lint_source(header + body) == []
    # beyond the first ten lines the directive is ignored
    late = body + "\n\n" + header
    assert "GC-A202" in rules_of(ast_lint.lint_source(late))


def test_filter_suppressed_matches_line():
    f = Finding("GC-A201", "msg", path="x.py", line=2)
    src = "a = 1\nb = 2  # graftcheck: disable=GC-A201\n"
    assert filter_suppressed([f], src) == []
    assert filter_suppressed([Finding("GC-A201", "msg", path="x.py",
                                      line=1)], src) != []


# ---------------------------------------------------------------------------
# the repo is clean under its own linter (tentpole acceptance)
# ---------------------------------------------------------------------------


def test_repo_static_pass_clean():
    paths = [os.path.join(REPO, "sparkflow_tpu"),
             os.path.join(REPO, "examples")]
    fs = run_static(paths)
    assert fs == [], "\n" + "\n".join(f.render() for f in fs)


def test_repo_jaxpr_self_check_clean():
    fs = repo_self_check()
    assert fs == [], "\n" + "\n".join(f.render() for f in fs)


def test_cli_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return float(x)\n")
    assert cli_main([str(bad), "--no-trace"]) == 1
    out = capsys.readouterr().out
    assert "GC-A201" in out
    good = tmp_path / "good.py"
    good.write_text("def f(x):\n    return x\n")
    assert cli_main([str(good), "--no-trace"]) == 0
    assert cli_main(["--list-rules"]) == 0
    listing = capsys.readouterr().out
    for rule in RULES:
        assert rule in listing


def test_cli_json_format(tmp_path, capsys):
    # JSONL contract: ONE finding object per line, so CI/editors can
    # stream-parse and grep; a clean run emits nothing on stdout
    import json

    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return float(x)\n")
    assert cli_main([str(bad), "--no-trace", "--format", "json"]) == 1
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    payload = [json.loads(ln) for ln in lines]  # every line parses alone
    assert payload and payload[0]["rule"] == "GC-A201"
    assert {"rule", "name", "path", "line", "source", "message"} \
        <= set(payload[0])
    assert cli_main([str(bad), "--no-trace", "--ignore", "GC-A201"]) == 0
    capsys.readouterr()
    good = tmp_path / "good.py"
    good.write_text("def f(x):\n    return x\n")
    assert cli_main([str(good), "--no-trace", "--format", "json"]) == 0
    assert capsys.readouterr().out.strip() == ""


# ---------------------------------------------------------------------------
# policy_lint: planted defects both directions + repo-clean gate (GC-S501)
# ---------------------------------------------------------------------------


def test_s501_impure_policy_detected():
    # planted defects: every category of impurity inside a marked module
    # must be flagged with the documented rule id
    from sparkflow_tpu.analysis import policy_lint

    src = textwrap.dedent("""\
        # graftcheck: pure-policy
        import time
        import random as rnd
        from socket import create_connection

        def decide(views):
            now = time.monotonic()
            coin = rnd.random()
            create_connection(("h", 80))
            open("/tmp/x")
            client.sleep(1.0)
            return now + coin
    """)
    fs = policy_lint.lint_source(src, "planted.py")
    assert fs and rules_of(fs) == {"GC-S501"}
    lines = {f.line for f in fs}
    # imports (2, 3, 4), time call (7), random call (8), socket call (9),
    # open (10), .sleep (11)
    assert {2, 3, 4, 7, 8, 9, 10, 11} <= lines


def test_s501_clean_and_unmarked_not_flagged():
    # the other direction: pure code in a marked module is clean, and an
    # unmarked module may be as impure as it likes (out of scope)
    from sparkflow_tpu.analysis import policy_lint

    pure = textwrap.dedent("""\
        # graftcheck: pure-policy
        from dataclasses import dataclass

        def pick(views, now, prefer_canary):
            return sorted(v.index for v in views if v.healthy)
    """)
    assert policy_lint.lint_source(pure, "pure.py") == []
    impure_unmarked = "import time\n\ndef f():\n    return time.time()\n"
    assert policy_lint.lint_source(impure_unmarked, "um.py") == []
    # standard suppression syntax applies
    suppressed = textwrap.dedent("""\
        # graftcheck: pure-policy
        import time  # graftcheck: disable=GC-S501

        def f(x):
            return x
    """)
    assert policy_lint.lint_source(suppressed, "sup.py") == []


def test_s501_policy_module_repo_clean():
    # the real policy module carries the marker and must stay pure; the
    # full static pass (which now includes policy_lint) agrees
    from sparkflow_tpu.analysis import policy_lint
    from sparkflow_tpu.serving import policies as policies_mod

    path = policies_mod.__file__
    with open(path, "r", encoding="utf-8") as fh:
        src = fh.read()
    assert policy_lint.PURE_POLICY_MARKER in src.splitlines()[0]
    assert policy_lint.lint_file(path) == []
    assert [f for f in run_static([path])] == []
