"""MFU diagnosis sweep for the BERT-base seq-512 train step (TPU).

Isolates the suspected non-matmul costs one at a time and prints one JSON
line per variant so the MFU gap (estimated ~24% on v5e in round 2) can be
attributed instead of guessed at:

- batch size (16 / 32 / 64 / 128): MXU utilization rises with larger
  effective matmul M-dims until HBM pressure bites
- dropout off vs on: how much of the step is threefry mask generation
  (24 [B,S,H]-sized bernoulli draws per step) + the where-multiply
- rbg vs threefry dropout keys: the hardware PRNG costs a fraction of
  threefry's VPU work; typed keys carry their impl through split/bernoulli
- attention off the pallas kernel (force_xla): whether flash is winning
  or losing vs XLA's fused attention at seq 512
- flash block_q x block_k variants at seq 512

Usage: python benchmarks/mfu_sweep.py [--quick]
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

QUICK = "--quick" in sys.argv


def main():
    import jax
    import jax.numpy as jnp
    import optax

    from sparkflow_tpu.models import build_registry_spec, model_from_json
    from sparkflow_tpu.optimizers import build_optimizer
    from sparkflow_tpu.utils.flops import (device_peak_flops, mfu,
                                           transformer_train_step_flops)

    on_tpu = jax.default_backend() == "tpu"
    if QUICK:
        cfg = dict(vocab_size=1000, hidden=128, num_layers=2, num_heads=4,
                   mlp_dim=256, max_len=128)
    else:
        cfg = dict(vocab_size=30522, hidden=768, num_layers=12, num_heads=12,
                   mlp_dim=3072, max_len=512)
    compute_dtype = "bfloat16" if on_tpu else None
    peak = device_peak_flops()
    rs = np.random.RandomState(0)
    n_steps = 2 if QUICK else 8

    def measure(B, dropout, rng_impl="threefry2x32", force_xla_attn=False,
                block_q=None, block_k=None):
        from sparkflow_tpu.ops.attention import force_xla_attention
        import contextlib

        m = model_from_json(
            build_registry_spec("transformer_classifier", num_classes=2,
                                dropout=dropout, **cfg),
            compute_dtype=compute_dtype)
        if block_q or block_k:
            # pin the flash tile sizes via a wrapper around _attention
            from sparkflow_tpu.ops import attention as A

            def patched(q, k, v, mask, causal):
                return A.flash_attention(q, k, v, causal=causal, kv_mask=mask,
                                         block_q=block_q, block_k=block_k)
            m._attention = patched
        opt = build_optimizer("adam", 1e-4, None)

        def key(i):
            return jax.random.key(i, impl=rng_impl)

        params = m.init(jax.random.PRNGKey(0))
        state = opt.init(params)

        ctx = force_xla_attention() if force_xla_attn else contextlib.nullcontext()

        with ctx:
            @jax.jit
            def step(params, state, ids, y, rng):
                def lf(p):
                    return m.loss_vector(p, {"input_ids": ids, "y": y},
                                         train=True, rng=rng).mean()
                loss, g = jax.value_and_grad(lf)(params)
                u, state2 = opt.update(g, state, params)
                return optax.apply_updates(params, u), state2, loss

            def batch(i):
                return (jnp.asarray(rs.randint(0, cfg["vocab_size"],
                                               (B, cfg["max_len"])), jnp.int32),
                        jnp.asarray(np.eye(2)[rs.randint(0, 2, B)], jnp.float32))

            ids, y = batch(0)
            params, state, loss = step(params, state, ids, y, key(0))
            jax.block_until_ready(params)
            from sparkflow_tpu.ops.attention import last_attention_path
            attn_path = last_attention_path()  # what actually traced
            t0 = time.perf_counter()
            for i in range(n_steps):
                ids, y = batch(i + 1)
                params, state, loss = step(params, state, ids, y, key(i + 1))
            jax.block_until_ready(params)
        dt = (time.perf_counter() - t0) / n_steps
        fl = transformer_train_step_flops(
            B, cfg["max_len"], cfg["hidden"], cfg["num_layers"],
            cfg["mlp_dim"], num_classes=2)
        rec = {"batch": B, "dropout": dropout, "rng": rng_impl,
               # the path flash_attention ACTUALLY traced, not the requested
               # one: a tile-rule fallback must not misattribute the delta
               "attn": attn_path,
               "requested": ("xla" if force_xla_attn else
                             f"pallas{block_q or ''}x{block_k or ''}"),
               "ms_per_step": round(dt * 1e3, 1),
               "examples_per_sec": round(B / dt, 1),
               "tflops_per_sec": round(fl / dt / 1e12, 2)}
        u = mfu(fl / dt, peak)
        if u is not None:
            rec["mfu"] = round(u, 4)
        print(json.dumps(rec), flush=True)
        return dt

    B0 = 8 if QUICK else 32
    if "--trace" in sys.argv:
        # one profiled measurement for hotspot attribution (open the
        # resulting trace in Perfetto / tensorboard)
        from sparkflow_tpu.utils.tracing import trace
        with trace("/tmp/mfu_trace"):
            measure(B0, dropout=0.1)
        print(json.dumps({"trace_written": "/tmp/mfu_trace"}), flush=True)
        return
    # batch ladder (the first lever)
    for B in ((4, 8) if QUICK else (16, 32, 64, 128)):
        try:
            measure(B, dropout=0.1)
        except Exception as e:  # OOM at the top end is informative, not fatal
            print(json.dumps({"batch": B, "error": str(e)[:200]}), flush=True)
    # dropout cost: off entirely, then cheap hardware PRNG
    measure(B0, dropout=0.0)
    measure(B0, dropout=0.1, rng_impl="rbg")
    # attention path: XLA blockwise vs pallas, plus tile variants
    measure(B0, dropout=0.1, force_xla_attn=True)
    if not QUICK:
        for bq, bk in ((256, 512), (512, 256), (256, 256)):
            measure(B0, dropout=0.1, block_q=bq, block_k=bk)


if __name__ == "__main__":
    main()
