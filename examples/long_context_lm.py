"""Long-context causal LM with ring-attention sequence parallelism.

Demonstrates the framework's long-context path: the sequence axis shards over
an ``sp`` mesh ring, K/V blocks rotate over ICI, and per-device memory is
O(S / n_devices) — contexts far beyond one chip's HBM train without code
changes. Runs on the virtual CPU mesh for demonstration; the same code spans a
real pod slice.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np


def main():
    import jax

    if jax.device_count() < 4:
        raise SystemExit(
            "this demo needs a mesh of at least 4 devices; on the CPU run it "
            "with JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")

    import jax.numpy as jnp
    from sparkflow_tpu.models import build_registry_spec, model_from_json
    from sparkflow_tpu.optimizers import build_optimizer
    from sparkflow_tpu.parallel.mesh import make_mesh
    from sparkflow_tpu.parallel.sp import make_sp_train_step

    smoke = bool(os.environ.get("SPARKFLOW_TPU_SMOKE"))
    sp = 4
    dp = max(1, jax.device_count() // sp)
    seq = 512 if smoke else 8192          # global context length
    spec = build_registry_spec(
        "transformer_lm", vocab_size=512,
        hidden=64 if smoke else 512,
        num_layers=2 if smoke else 8,
        num_heads=4 if smoke else 8,
        mlp_dim=128 if smoke else 2048,
        # 'dots' saves matmul outputs and recomputes only the cheap
        # elementwise ops — far less backward recompute than full remat,
        # still bounded activation memory at long sequence lengths
        max_len=seq, dropout=0.0, remat="dots" if not smoke else False)

    lm = model_from_json(spec)
    mesh = make_mesh({"dp": dp, "sp": sp})
    print(f"mesh: dp={dp} x sp={sp}, context length {seq}")

    optimizer = build_optimizer("adam", 3e-4, None)
    params = lm.init(jax.random.PRNGKey(0))
    opt_state = optimizer.init(params)
    step = make_sp_train_step(lm, optimizer, mesh)

    rs = np.random.RandomState(0)
    batch = 2 * dp
    for i in range(3):
        ids = jnp.asarray(rs.randint(0, 512, (batch, seq)), jnp.int32)
        mask = jnp.ones((batch, seq), jnp.float32)
        params, opt_state, loss = step(params, opt_state, ids, mask,
                                       jax.random.PRNGKey(i))
        print(f"step {i}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
