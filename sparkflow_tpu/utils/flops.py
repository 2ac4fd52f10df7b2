"""FLOPs and MFU accounting for ``Trainer.fit(trace_spans=True)``'s step
stats.

Model-FLOPs utilization anchors a throughput to the hardware roofline, so a
number that looks big but wastes the MXU is visible as such. The benchmark
keeps its own copy of these counts (``chipbench/counts.py``), so that a
change here cannot move ``mfu.train``.

Two FLOPs sources, used deliberately:

- :func:`jit_flops` — XLA's own cost model for a compiled step
  (``Compiled.cost_analysis()['flops']``). Exact for pure-XLA models
  (MLP / CNN / autoencoder / ResNet). NOT usable when the hot op is a pallas
  kernel: custom calls report zero flops, so the count silently undercounts.
- :func:`transformer_train_step_flops` — the standard analytic count
  (2·tokens·matmul-params forward, backward = 2× forward, plus the two
  attention matmuls) for transformer steps whose attention runs in pallas.

MFU convention: model FLOPs (the useful work), not hardware FLOPs — remat
replays and padding don't earn credit.
"""

from __future__ import annotations

from typing import Optional

# Peak *bf16* matmul throughput per chip, TFLOP/s. Source: Google Cloud TPU
# documentation, "TPU v2/v3/v4/v5e/v5p/v6e" system-architecture pages
# (v5e: 197 TFLOP/s bf16 per chip). Keys are substrings matched (lowercased)
# against ``jax.devices()[0].device_kind``. Order matters: more specific
# first.
_PEAK_BF16_TFLOPS = (
    ("v6e", 918.0),  # Trillium
    ("v6", 918.0),
    ("v5 lite", 197.0),
    ("v5litepod", 197.0),
    ("v5e", 197.0),
    ("v5p", 459.0),
    ("v5", 459.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def device_peak_flops() -> Optional[float]:
    """Peak bf16 FLOP/s of the first device, or None off-TPU (an MFU against
    a CPU 'peak' would be noise, not signal). A TPU whose ``device_kind`` is
    not in the table is an error: a guessed peak reports a wrong MFU with no
    indication."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    kind = (getattr(dev, "device_kind", "") or "").lower()
    for key, tflops in _PEAK_BF16_TFLOPS:
        if key in kind:
            return tflops * 1e12
    raise ValueError(
        f"TPU device_kind {dev.device_kind!r} is not in the peak table "
        f"(sparkflow_tpu/utils/flops.py:_PEAK_BF16_TFLOPS); add it with its "
        f"source rather than assuming one")


def jit_flops(fn, *args) -> Optional[float]:
    """FLOPs of one call of ``fn(*args)`` per XLA's cost analysis, or None
    when unavailable. Do not use on programs whose hot op is a pallas custom
    call (reported as zero flops) — see module docstring."""
    import jax

    try:
        compiled = jax.jit(fn).lower(*args).compile()
        ca = compiled.cost_analysis()
        flops = float(ca.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


def train_step_flops(model, input_name, label_name, optimizer,
                     x, y=None) -> Optional[float]:
    """Cost-analyze ONE synchronous train step (value_and_grad + optimizer
    update) of a GraphModel at the given batch, without executing it.
    Suitable for pure-XLA models; returns None if analysis fails."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..core import make_loss_fn, _step_body

    loss_fn = make_loss_fn(model, input_name, label_name)
    step = _step_body(loss_fn, optimizer)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = optimizer.init(params)
    n = (x[0] if isinstance(x, tuple) else x).shape[0]
    xd = (tuple(jnp.asarray(a) for a in x) if isinstance(x, tuple)
          else jnp.asarray(x))
    yd = jnp.asarray(y) if y is not None else jnp.zeros((n, 1), jnp.float32)
    mask = jnp.ones((n,), jnp.float32)
    rng = jax.random.PRNGKey(0)
    return jit_flops(step, params, opt_state, xd, yd, mask, rng)


def transformer_train_step_flops(batch: int, seq: int, hidden: int,
                                 num_layers: int, mlp_dim: int,
                                 vocab_size: int = 0, num_classes: int = 0,
                                 causal: bool = False) -> float:
    """Analytic model FLOPs for one transformer train step (fwd + bwd).

    Matmul forward = 2 · tokens · matmul-params (qkv/out projections + MLP,
    plus the LM head / classifier head when given); attention forward =
    2 · 2 · B · S² · hidden per layer (QKᵀ and PV), halved when causal.
    Backward = 2 × forward; embedding gathers are free.
    """
    p_mm = num_layers * (4 * hidden * hidden + 2 * hidden * mlp_dim)
    if vocab_size:
        p_mm += hidden * vocab_size  # LM head matmul (tied or not, it runs)
    if num_classes:
        p_mm += hidden * num_classes
    tokens = batch * seq
    fwd = 2.0 * tokens * p_mm
    fwd += 4.0 * batch * seq * seq * hidden * num_layers * (
        0.5 if causal else 1.0)
    return 3.0 * fwd


def attention_flops(batch: int, heads: int, seq_q: int, seq_k: int,
                    head_dim: int, causal: bool = False,
                    with_backward: bool = False) -> float:
    """Analytic FLOPs of one attention call: QKᵀ and PV matmuls
    (2 · 2 · B · H · Sq · Sk · D forward), halved for causal masking;
    backward re-runs both plus dQ/dK/dV (≈ 2× forward)."""
    fwd = 4.0 * batch * heads * seq_q * seq_k * head_dim * (
        0.5 if causal else 1.0)
    return fwd * (3.0 if with_backward else 1.0)


def mfu(flops_per_sec: Optional[float],
        peak: Optional[float] = None) -> Optional[float]:
    """Model-FLOPs utilization, or None when either side is unknown
    (off-TPU, or the FLOPs count failed). Nominally in [0, 1]; a value > 1
    means the FLOPs count or the peak table is wrong — warn loudly but return
    the raw ratio so the bad input is visible rather than clamped away."""
    if flops_per_sec is None:
        return None
    if peak is None:
        peak = device_peak_flops()
    if not peak:
        return None
    u = flops_per_sec / peak
    if u > 1.0:
        import logging
        logging.getLogger(__name__).warning(
            "MFU %.3f > 1: the FLOPs count or the device peak (%.0f TFLOP/s) "
            "is wrong — check device_peak_flops()'s table against this chip",
            u, peak / 1e12)
    return u
