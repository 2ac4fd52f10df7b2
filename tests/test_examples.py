"""examples/ must RUN, not just compile.

The reference treats its examples as Docker smoke tests
(``/root/reference/Makefile:4-11``, ``.travis.yml:15-19``); mirroring that,
every example executes end-to-end here — ``main`` path, fit, transform,
save/load — as a subprocess on the virtual CPU mesh in SPARKFLOW_TPU_SMOKE
mode (tiny iters/rows; the knob each example honors). A broken example turns
CI red instead of shipping green behind a string grep.

Structural pins stay too: the repo-root sys.path bootstrap (directly
runnable from any cwd), and no example decides its own backend — none probes
the accelerator and demotes itself to the CPU, none forces a platform in
code. An example that wants the CPU is run with ``JAX_PLATFORMS=cpu``.
"""

import os
import py_compile
import re
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")


def _example_files():
    return sorted(f for f in os.listdir(EXAMPLES) if f.endswith(".py"))


@pytest.mark.parametrize("fname", _example_files())
def test_example_compiles(fname):
    py_compile.compile(os.path.join(EXAMPLES, fname), doraise=True)


@pytest.mark.parametrize("fname", _example_files())
def test_example_has_path_bootstrap(fname):
    src = open(os.path.join(EXAMPLES, fname)).read()
    assert "sys.path.insert" in src, (
        f"{fname} lacks the repo-root sys.path bootstrap; "
        f"`python examples/{fname}` would fail with ModuleNotFoundError")


_FORCES_PLATFORM = re.compile(
    r"""environ\[\s*["']JAX_PLATFORMS["']\s*\]\s*="""
    r"""|environ\.setdefault\(\s*["']JAX_PLATFORMS"""
    r"""|config\.update\(\s*["']jax_platforms?["']"""
    r"""|["']JAX_PLATFORMS["']\s*:""")


@pytest.mark.parametrize("fname", _example_files())
def test_example_never_picks_its_own_backend(fname):
    src = open(os.path.join(EXAMPLES, fname)).read()
    assert "ensure_live_backend" not in src and "tpu_alive" not in src, (
        f"{fname} probes the backend and falls back to the CPU; a run that "
        f"was meant for the chip would then report success from the CPU")
    forced = _FORCES_PLATFORM.search(src)
    assert forced is None, (
        f"{fname} forces a platform in code ({forced.group(0)!r}); run it "
        f"with JAX_PLATFORMS=cpu instead")


@pytest.mark.slow  # full end-to-end subprocess train per example: minutes of
# wall clock across the matrix — out of the tier-1 budget, run with `-m slow`
@pytest.mark.parametrize("fname", _example_files())
def test_example_executes(fname, tmp_path):
    """Run the example's real ``__main__`` path to completion (smoke mode,
    CPU mesh, cwd=tmp so save artifacts don't litter the repo)."""
    env = dict(os.environ)
    env.update({
        "SPARKFLOW_TPU_SMOKE": "1",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    })
    env.pop("PYTHONPATH", None)  # examples bootstrap their own sys.path
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, fname)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, (
        f"{fname} failed (rc={proc.returncode}):\n--- stdout ---\n"
        f"{proc.stdout[-3000:]}\n--- stderr ---\n{proc.stderr[-3000:]}")
