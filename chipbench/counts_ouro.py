"""Operations of the ``ouro`` family's looped training step, for its
per-layer metric ``mfu_loop.train``.

Convention, as in ``counts.py`` and its siblings: *model* work, the least the
mathematics needs. Every pass of the stack is work the model asks for (the
weights are shared, the work is not), so the blocks count ``total_ut_steps``
times and so does the head; what a checkpoint makes again in the backward
pass (a block's forward, the head's logits) earns nothing, nor do the masked
pairs inside a causal tile. Left out as too small to show: the exit gate
(``2 x hidden`` a token and pass), norms, rotary positions, the softmaxes.
"""

from __future__ import annotations

from typing import Dict


def sizes(cfg: dict) -> dict:
    return dict(h=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                d=cfg["head_dim"], m=cfg["intermediate_size"],
                vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
                passes=cfg["total_ut_steps"])


def projection_params(cfg: dict) -> Dict[str, int]:
    """Matrix-product parameters a token passes through in one layer (q, k,
    v, o; gate, up, down) and in the head."""
    z = sizes(cfg)
    return dict(attention=4 * z["h"] * z["heads"] * z["d"],
                mlp=3 * z["h"] * z["m"], head=z["h"] * z["vocab"])


def causal_pairs(seq_len: int) -> int:
    """(query, key) pairs of one row and head that a causal mask leaves."""
    return seq_len * (seq_len + 1) // 2


def forward_flops_per_token(cfg: dict, seq_len: int) -> Dict[str, float]:
    """Forward model FLOPs a token, by part, over all passes: two a
    multiply-add; attention's two products (``QK^T``, ``PV``) over the causal
    pairs."""
    z, p = sizes(cfg), projection_params(cfg)
    times = z["layers"] * z["passes"]
    return dict(
        blocks=2.0 * (p["attention"] + p["mlp"]) * times,
        attention=4.0 * z["heads"] * z["d"] * causal_pairs(seq_len)
        / seq_len * times,
        heads=2.0 * p["head"] * z["passes"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs per trained token, forward and backward (everything has a
    gradient: backward is twice the forward)."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())
