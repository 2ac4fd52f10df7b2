"""ZeRO stage sweep smoke: one Trainer fit per zero stage 0-3, same data,
same seed, under one declarative :class:`ShardingConfig`.

Run via ``make zero-smoke`` (or directly). The script

1. spins up 8 virtual CPU devices and a ``{'dp': 8}`` mesh;
2. trains the same MLP at ``zero_stage`` 0, 1, 2 and 3 — the stage is the
   ONLY thing that changes between runs (``ShardingConfig(zero_stage=s)``);
3. asserts per-epoch loss and final-param parity across all four stages
   (the stages are the same math on different layouts; differences are
   reduction-order-bounded);
4. round-trips a stage-3 checkpoint through a stage-0 restore and asserts
   the params are bit-identical (checkpoints always hold the standard
   layout, so any stage restores at any other);
5. prints the structural memory report — grad+opt bytes live at update
   time per stage — showing the 1/dp shrink the stages buy.

Everything runs on CPU (`JAX_PLATFORMS=cpu`) in under a minute.
"""

import os
import shutil
import sys
import tempfile

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from sparkflow_tpu.models.presets import mlp
from sparkflow_tpu.optimizers import build_optimizer
from sparkflow_tpu.optimizers_sharded import zero_memory_report
from sparkflow_tpu.parallel.mesh import make_mesh
from sparkflow_tpu.sharding import ShardingConfig
from sparkflow_tpu.trainer import Trainer

ATOL = 5e-5
DP = 8


def fit(stage, ckpt_dir=None, iters=4):
    t = Trainer(mlp(10, 3, hidden=(17,)), "x:0", "y:0", optimizer="adam",
                learning_rate=1e-2, mini_batch_size=16, iters=iters, seed=3,
                mesh=make_mesh({"dp": DP}),
                sharding=ShardingConfig(zero_stage=stage),
                checkpoint_dir=ckpt_dir,
                checkpoint_every=1 if ckpt_dir else 0)
    rs = np.random.RandomState(0)
    X = rs.randn(64, 10).astype(np.float32)
    Y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 64)]
    return t, t.fit(X, Y)


def main():
    results = {s: fit(s) for s in (0, 1, 2, 3)}
    base = results[0][1]
    print(f"stage 0 losses: {[round(l, 6) for l in base.losses]}")
    for s in (1, 2, 3):
        r = results[s][1]
        dl = max(abs(a - b) for a, b in zip(base.losses, r.losses))
        dp_ = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                  zip(jax.tree.leaves(base.params), jax.tree.leaves(r.params)))
        print(f"stage {s}: max dloss={dl:.2e} max dparam={dp_:.2e}")
        assert dl < ATOL and dp_ < ATOL, f"stage {s} parity FAILED"

    # checkpoint interchange: write at stage 3, restore at stage 0
    d = tempfile.mkdtemp(prefix="zero_smoke_")
    try:
        t3, _ = fit(3, ckpt_dir=d, iters=2)
        t0b = Trainer(mlp(10, 3, hidden=(17,)), "x:0", "y:0",
                      optimizer="adam", learning_rate=1e-2,
                      mini_batch_size=16, iters=2, seed=3,
                      mesh=make_mesh({"dp": DP}),
                      sharding=ShardingConfig(zero_stage=0),
                      checkpoint_dir=d, checkpoint_every=1)
        rs = np.random.RandomState(0)
        X = rs.randn(64, 10).astype(np.float32)
        Y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 64)]
        t0b.fit(X, Y)  # resumes at the saved epoch; runs nothing new
        db = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                 zip(jax.tree.leaves(t3.params), jax.tree.leaves(t0b.params)))
        assert db == 0.0, f"stage3->stage0 restore not bit-identical ({db})"
        print("checkpoint stage3 -> stage0 restore: bit-identical")
    finally:
        shutil.rmtree(d, ignore_errors=True)

    # structural memory: grad+opt bytes live at update time, per stage
    opt = build_optimizer("adam", 1e-2, None)
    from sparkflow_tpu.models import model_from_json
    p0 = model_from_json(mlp(10, 3, hidden=(17,))).init(jax.random.PRNGKey(0))
    print(f"{'stage':>5} {'grad+opt @update':>18} {'params @rest':>14}")
    for s in (0, 1, 2, 3):
        rep = zero_memory_report(opt, p0, DP, s)
        print(f"{s:>5} {rep['grad_opt_at_update']:>18} "
              f"{rep['params_at_rest']:>14}")
    print("zero-smoke OK: stages 0-3 agree; checkpoints interchange")


if __name__ == "__main__":
    main()
