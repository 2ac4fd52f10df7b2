"""``chip_smoke.py --rehearse``: the chip smoke's phases at toy widths on the
CPU, run in-process, plus the rules around it — no TPU and no ``--rehearse``
is a failure, a failed phase is a non-zero exit, and the compile cache goes
where ``JAX_COMPILATION_CACHE_DIR`` says or to ``<checkout>/.jax_cache``."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_rehearse_estimator_phase():
    line = chip_smoke.phase_estimator(chip_smoke.REHEARSE)
    assert line["phase"] == "estimator" and line["roundtrip_equal"]
    assert line["train_accuracy"] > 0.3
    json.dumps(line)


@pytest.fixture(scope="module")
def train_line():
    return chip_smoke.phase_train(chip_smoke.REHEARSE)


def test_rehearse_train_phase(train_line):
    line = train_line
    assert line["phase"] == "train" and line["attention_path"] == "pallas"
    assert line["traces"] == {"run": 1}
    assert line["traces_after_first_fit"] == {}
    assert line["losses"][-1] < line["losses"][0]


def test_rehearse_serve_phase():
    lines = chip_smoke.phase_serve(chip_smoke.REHEARSE)
    assert [l["engine"].get("kv_quant") for l in lines] == [None, "int8", None]
    assert [l["engine"].get("spec_k") for l in lines] == [None, None, 4]
    for line in lines:
        assert line["phase"] == "serve" and line["attention_path"] == "pallas"
        assert line["attention_paths"]["step"] == ["paged_attention:pallas"]
        assert line["steady_traces"] == 0
        assert line["greedy_worst_logit_gap"] <= line["logit_tolerance"]
    assert lines[0]["prefix_hits"] > 0
    assert lines[2]["attention_paths"]["verify"] == [
        "paged_attention_verify:pallas"]
    assert lines[2]["spec"]["proposed"] > 0


def test_rehearse_multichip_phase(train_line):
    """dp=4 on four of the virtual devices against one device."""
    line = chip_smoke.phase_multichip(chip_smoke.REHEARSE, one=train_line)
    assert line["mesh"] == {"dp": 4}
    assert line["max_loss_delta"] <= line["loss_tolerance"]


def _last_line(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def test_cpu_without_rehearse_fails():
    """As the driver runs it, in a sandbox: no accelerator, no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    last = _last_line(proc.stdout)
    assert last["ok"] is False and "no TPU" in last["error"]
    assert last["device"]["platform"] == "cpu"


@pytest.fixture
def no_cache_side_effect(monkeypatch):
    """``main`` turns the persistent cache on; keep that out of this
    worker's later tests."""
    from sparkflow_tpu.utils import hw
    monkeypatch.setattr(hw, "enable_compilation_cache",
                        lambda path=None: "<not enabled under test>")


def test_failed_phase_is_a_nonzero_exit(monkeypatch, capsys,
                                        no_cache_side_effect):
    def broken(cfg):
        chip_smoke.check(False, "the train step traced attention path "
                                "'reference', not 'pallas'")

    monkeypatch.setattr(chip_smoke, "phase_estimator", lambda cfg: None)
    monkeypatch.setattr(chip_smoke, "phase_train", broken)
    assert chip_smoke.main(["--rehearse"]) == 1
    last = _last_line(capsys.readouterr().out)
    assert last["ok"] is False and "'reference'" in last["error"]


def test_ok_line_is_the_contracts(monkeypatch, capsys, no_cache_side_effect):
    for phase in ("phase_estimator", "phase_train", "phase_serve"):
        monkeypatch.setattr(chip_smoke, phase, lambda cfg: None)
    assert chip_smoke.main(["--rehearse"]) == 0
    d = jax.devices()[0]
    assert _last_line(capsys.readouterr().out) == {
        "ok": True, "device": {"platform": d.platform, "kind": d.device_kind,
                               "count": len(jax.devices())}}


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])
    compilation_cache.reset_cache()


def test_cache_dir_yields_to_the_environment(monkeypatch, tmp_path,
                                             restore_cache_config):
    from sparkflow_tpu.utils.hw import enable_compilation_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "given"))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compilation_cache() == str(tmp_path / "given")
    assert enable_compilation_cache(str(tmp_path / "mine")) == str(
        tmp_path / "given")
    # no directory was set in code, and none was made
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "mine").exists()


def test_cache_dir_defaults_into_the_checkout(monkeypatch, tmp_path,
                                              restore_cache_config):
    from sparkflow_tpu.utils import hw
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(hw, "DEFAULT_CACHE_DIR", str(tmp_path / ".jax_cache"))
    assert hw.enable_compilation_cache() == str(tmp_path / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / ".jax_cache")
    assert (tmp_path / ".jax_cache").is_dir()
    assert hw.enable_compilation_cache(str(tmp_path / "mine")) == str(
        tmp_path / "mine")
    # the real default: fixed, inside the checkout, never the home directory
    monkeypatch.undo()
    assert hw.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
