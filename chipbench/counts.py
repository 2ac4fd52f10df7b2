"""Operations, bytes and peaks: the yardstick's arithmetic.

``transformer_train_step_flops`` and ``attention_flops`` are copies of
``sparkflow_tpu/utils/flops.py`` as of PR 21 (``chipbench/tests`` holds them
equal at GPT-2 medium); the benchmark keeps its own so that a later change
to the program's file cannot move ``mfu.train``. Convention: *model* FLOPs,
the useful work. Recomputation and padding earn no credit.
"""

from __future__ import annotations

import json
import os
from typing import Dict

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind`` (``peaks.json``).
    An unknown kind is an error: a guessed peak gives a wrong share with
    nothing to show for it."""
    with open(_PEAKS_FILE) as f:
        table = json.load(f)
    entry = table.get(device_kind)
    if not isinstance(entry, dict):
        known = sorted(k for k, v in table.items() if isinstance(v, dict))
        raise KeyError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json "
            f"(known: {known}); add it with its source")
    return entry


def transformer_train_step_flops(batch: int, seq: int, hidden: int,
                                 num_layers: int, mlp_dim: int,
                                 vocab_size: int = 0,
                                 causal: bool = False) -> float:
    """Analytic model FLOPs of one transformer train step (forward and
    backward). Matmul forward = 2 x tokens x matmul parameters (qkv and out
    projections, MLP, LM head); attention forward = 2 x 2 x B x S^2 x hidden
    per layer (QK^T and PV), halved when causal. Backward = 2 x forward;
    embedding gathers are free."""
    p_mm = num_layers * (4 * hidden * hidden + 2 * hidden * mlp_dim)
    if vocab_size:
        p_mm += hidden * vocab_size
    tokens = batch * seq
    fwd = 2.0 * tokens * p_mm
    fwd += 4.0 * batch * seq * seq * hidden * num_layers * (
        0.5 if causal else 1.0)
    return 3.0 * fwd


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs per trained token of a GPT-2 family configuration file at
    sequence length ``seq`` (causal)."""
    return transformer_train_step_flops(
        1, seq, cfg["n_embd"], cfg["n_layer"], cfg["n_inner"],
        vocab_size=cfg["vocab_size"], causal=True) / seq


def attention_flops(batch: int, heads: int, seq_q: int, seq_k: int,
                    head_dim: int, causal: bool = False,
                    with_backward: bool = False) -> float:
    """Analytic FLOPs of one attention call: QK^T and PV
    (2 x 2 x B x H x Sq x Sk x D forward), halved for causal masking; the
    backward pass runs both again plus dQ, dK, dV (about 2 x forward)."""
    fwd = 4.0 * batch * heads * seq_q * seq_k * head_dim * (
        0.5 if causal else 1.0)
    return fwd * (3.0 if with_backward else 1.0)
