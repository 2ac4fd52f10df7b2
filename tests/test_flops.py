"""FLOPs/MFU accounting (utils.flops): the roofline math behind step stats."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkflow_tpu.utils.flops import (attention_flops, device_peak_flops,
                                       jit_flops, mfu,
                                       transformer_train_step_flops,
                                       train_step_flops)


def test_jit_flops_counts_matmul():
    a = jnp.zeros((64, 128), jnp.float32)
    b = jnp.zeros((128, 32), jnp.float32)
    fl = jit_flops(lambda x, y: x @ y, a, b)
    # 2*m*k*n MACs-as-flops; XLA may count fused epilogue ops too
    assert fl is not None
    assert 0.9 * (2 * 64 * 128 * 32) <= fl <= 1.5 * (2 * 64 * 128 * 32)


def test_transformer_flops_formula():
    # BERT-base seq-512 batch-16: the canonical ~4.6e12 flops/step
    # (2*tokens*matmul-params fwd, bwd=2x, + attention matmuls)
    fl = transformer_train_step_flops(16, 512, 768, 12, 3072, num_classes=2)
    assert 4.0e12 < fl < 5.5e12
    # causal halves only the attention term
    causal = transformer_train_step_flops(16, 512, 768, 12, 3072,
                                          num_classes=2, causal=True)
    assert causal < fl
    diff = fl - causal
    attn_half = 0.5 * 3 * 4 * 16 * 512 * 512 * 768 * 12
    np.testing.assert_allclose(diff, attn_half, rtol=1e-6)


def test_attention_flops():
    fwd = attention_flops(2, 8, 4096, 4096, 64)
    assert fwd == 4.0 * 2 * 8 * 4096 * 4096 * 64
    assert attention_flops(2, 8, 4096, 4096, 64, causal=True) == fwd / 2
    assert attention_flops(2, 8, 4096, 4096, 64, with_backward=True) == 3 * fwd


def test_mfu_off_tpu_is_none():
    if jax.devices()[0].platform != "tpu":
        assert device_peak_flops() is None
        assert mfu(1e12) is None
    assert mfu(None, 197e12) is None
    assert mfu(98.5e12, 197e12) == 0.5


def test_train_step_flops_on_graph_model():
    import sparkflow_tpu.nn as nn
    from sparkflow_tpu.graph_utils import build_graph
    from sparkflow_tpu.graphdef import GraphModel
    from sparkflow_tpu.optimizers import build_optimizer

    def model():
        x = nn.placeholder([None, 32], name="x")
        y = nn.placeholder([None, 4], name="y")
        out = nn.dense(nn.dense(x, 64, activation="relu"), 4, name="out")
        nn.softmax_cross_entropy(y, out)

    m = GraphModel.from_json(build_graph(model))
    opt = build_optimizer("adam", 1e-3, None)
    rs = np.random.RandomState(0)
    x = rs.rand(128, 32).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rs.randint(0, 4, 128)]
    fl = train_step_flops(m, "x:0", "y:0", opt, x, y)
    assert fl is not None
    # fwd+bwd matmuls dominate; XLA drops the dead input-layer dx matmul,
    # so the floor is fwd + (2x fwd - dx1) ~ 2.1x forward matmul flops
    fwd_mm = 2 * 128 * (32 * 64 + 64 * 4)
    assert fl >= 2.0 * fwd_mm


def test_unknown_tpu_kind_is_an_error(monkeypatch):
    """A TPU missing from the peak table raises; it is never handed the
    v5e's peak (a guessed peak reports a wrong MFU with no indication)."""
    class _Dev:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v5 lite")])
    assert device_peak_flops() == 197e12
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v9 mystery")])
    with pytest.raises(ValueError, match="TPU v9 mystery"):
        device_peak_flops()
