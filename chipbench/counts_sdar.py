"""Operations and bytes of the ``sdar`` family's block-diffusion training
step, for its per-layer metrics (``block_attn_*_roofline.train``,
``mfu_blockdiff.train``).

Convention, as in ``counts.py`` and ``counts_keye_vl2.py``: *model* work, the
least the mathematics needs. Masked pairs inside a visited tile, recomputed
products (the backward kernels make the scores again) and padding rows of a
dropless layout earn no credit, so no share can pass 100 % unless a count
here is too high. Attention counts the pairs the rule leaves visible, the
experts the (position, expert) pairs that landed on the experts held here,
the head the noised half over the vocabulary held. A row is ``L`` tokens and
``2 L`` positions: per-token numbers are over ``L``.
"""

from __future__ import annotations

from typing import Dict


def sizes(cfg: dict) -> dict:
    return dict(h=cfg["hidden_size"], nq=cfg["num_attention_heads"],
                nkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
                e_all=cfg["published_num_experts"], held=cfg["num_experts"],
                per_tok=cfg["num_experts_per_tok"],
                m=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"], block=cfg["block_length"])


def visible_pairs(length: int, block: int) -> int:
    """(query, key) pairs of one row the rule leaves, a head: a clean query
    sees the clean keys of its own and earlier blocks, a noised one the clean
    keys of earlier blocks and the noised keys of its own."""
    blocks = length // block
    clean_clean = block * block * blocks * (blocks + 1) // 2
    noised_clean = block * block * blocks * (blocks - 1) // 2
    noised_noised = block * block * blocks
    return clean_clean + noised_clean + noised_noised


def projection_params(cfg: dict) -> Dict[str, int]:
    """Matrix-product parameters a position passes through in one layer, by
    part, and the head's."""
    z = sizes(cfg)
    return dict(
        attention=z["h"] * z["nq"] * z["d"] * 2 + z["h"] * z["nkv"] * z["d"] * 2,
        router=z["h"] * z["e_all"], expert=3 * z["h"] * z["m"],
        head=z["h"] * z["vocab"])


def kernel_work(cfg: dict, length: int, rows_per_step: int
                ) -> Dict[str, Dict[str, float]]:
    """``{kernel: {"flops", "bytes"}}`` of one optimizer step, for the
    kernels named as the program names them. bf16 operands (2 bytes), ``2 *
    length`` positions a row."""
    z = sizes(cfg)
    calls = rows_per_step * z["layers"]          # (row, layer) pairs a step
    seen = visible_pairs(length, z["block"])
    pair = 2 * z["nq"] * z["d"]                  # one product over one pair
    q_like = 2 * z["nq"] * 2 * length * z["d"]   # q, o, dO, dQ: bytes each
    k_like = 2 * z["nkv"] * 2 * length * z["d"]  # k, v, dK, dV
    return {
        # QK^T and PV; reads q, k, v, writes o
        "block_attn_fwd": dict(flops=2 * pair * seen * calls,
                               bytes=(2 * q_like + 2 * k_like) * calls),
        # dP = dO V^T and dQ = dS K; the scores made again earn nothing
        "block_attn_bwd_dq": dict(flops=2 * pair * seen * calls,
                                  bytes=(3 * q_like + 2 * k_like) * calls),
        # dV = P^T dO and dK = dS^T Q
        "block_attn_bwd_dkv": dict(flops=2 * pair * seen * calls,
                                   bytes=(2 * q_like + 4 * k_like) * calls),
    }


def train_flops_per_token(cfg: dict, length: int,
                          pairs_here_per_token: float) -> float:
    """Model FLOPs per trained token (``length`` a row) of the work done
    here, forward and backward. Forward, a layer: the projections and the
    router on both copies of the token, the experts here by the pairs that
    landed here, attention over the visible pairs; once, the head on the
    noised copy. Everything has a gradient: backward is twice the forward."""
    z = sizes(cfg)
    p = projection_params(cfg)
    attn = 4 * z["nq"] * z["d"] * visible_pairs(length, z["block"]) / length
    dense = 2 * (2 * (p["attention"] + p["router"])
                 + p["expert"] * pairs_here_per_token)
    return 3.0 * (z["layers"] * (dense + attn) + 2 * p["head"])
