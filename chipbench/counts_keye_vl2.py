"""Operations and bytes of the ``keye_vl2`` family's training step, for its
per-layer metrics (``*_roofline.train``, ``mfu_active.train``).

Convention, as in ``counts.py``: *model* work, the least the mathematics
needs. Recomputation (the blocks are rematerialised, so every forward kernel
runs twice a step), masked-out pairs inside a tile, padding rows of a
dropless layout and tiles that are skipped earn no credit, so no share can
pass 100 % unless a count here is too high. Attention counts the *selected*
(query, key) pairs, the experts the (token, expert) pairs that landed on the
experts held here, the head the vocabulary held.
"""

from __future__ import annotations

from typing import Dict


def sizes(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    return dict(h=cfg["hidden_size"], nq=cfg["num_attention_heads"],
                nkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
                ni=sa["indexer_num_heads"], di=sa["indexer_head_dim"],
                topk=sa["topk"], e_all=cfg["num_local_experts"],
                held=cfg["num_experts"], per_tok=cfg["num_experts_per_tok"],
                m=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"])


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs one row of ``seq`` tokens attends: every ``s <= t``
    while ``t < topk``, then ``topk`` a query."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def projection_params(cfg: dict) -> Dict[str, int]:
    """Matrix-product parameters a token passes through in one layer, by
    part, and the head's."""
    z = sizes(cfg)
    return dict(
        attention=z["h"] * z["nq"] * z["d"] * 2 + z["h"] * z["nkv"] * z["d"] * 2,
        indexer=z["h"] * (z["ni"] * z["di"] + z["di"] + z["ni"]),
        router=z["h"] * z["e_all"],
        expert=3 * z["h"] * z["m"],
        head=z["h"] * z["vocab"])


def kernel_work(cfg: dict, seq: int, rows_per_step: int,
                pairs_here_per_step: float) -> Dict[str, Dict[str, float]]:
    """``{kernel: {"flops", "bytes"}}`` of one optimizer step, for the
    kernels named as the program names them. bf16 operands (2 bytes)."""
    z = sizes(cfg)
    calls = rows_per_step * z["layers"]          # (row, layer) pairs a step
    sel = selected_pairs(seq, z["topk"])
    qkvo = 2 * (2 * z["nq"] + 2 * z["nkv"]) * seq * z["d"]   # q, o, k, v once
    mask = seq * seq                                       # int8
    pair = 2 * z["nq"] * z["d"]                  # one product over one pair
    gemm = 2 * z["h"] * z["m"] * pairs_here_per_step * z["layers"]
    w_bytes = 2 * z["held"] * z["h"] * z["m"] * z["layers"]
    row_bytes = 2 * (z["h"] + z["m"]) * pairs_here_per_step * z["layers"]
    return {
        # QK^T and PV over the selected pairs
        "sparse_attn_fwd": dict(flops=2 * pair * sel * calls,
                                bytes=(qkvo + mask) * calls),
        # dP = dO V^T and dQ = dS K; the recomputed QK^T earns nothing
        "sparse_attn_bwd_dq": dict(flops=2 * pair * sel * calls,
                                   bytes=(qkvo + mask) * calls),
        # dV = P^T dO and dK = dS^T Q
        "sparse_attn_bwd_dkv": dict(flops=2 * pair * sel * calls,
                                    bytes=(qkvo + mask) * calls),
        # QK^T once more for the indexer's target, written as [S, S] f32
        "sparse_attn_probs": dict(flops=pair * sel * calls,
                                  bytes=(qkvo // 2 + mask + 4 * seq * seq)
                                  * calls),
        # forward W1, W3, W2 and the three input gradients
        "expert_gmm": dict(flops=6 * gemm, bytes=6 * (w_bytes + row_bytes)),
        # the three weight gradients
        "expert_tgmm": dict(flops=3 * gemm, bytes=3 * (w_bytes + row_bytes)),
    }


def train_flops_per_token(cfg: dict, seq: int,
                          pairs_here_per_token: float) -> float:
    """Model FLOPs per trained token of the work done here, forward and
    backward. Forward, a token a layer: the projections, the indexer's
    projections and its scores over every causal pair, attention and the
    indexer's target over the selected pairs, the router, the experts here;
    once, the head. Backward is twice the forward of what has a gradient:
    not the selection (discrete) nor the target (``stop_gradient``), and the
    indexer's loss reaches its scores over the selected pairs only."""
    z = sizes(cfg)
    p = projection_params(cfg)
    sel = selected_pairs(seq, z["topk"]) / seq
    causal = causal_pairs(seq) / seq
    attn = 4 * z["nq"] * z["d"] * sel
    target = 2 * z["nq"] * z["d"] * sel
    scores = 2 * z["ni"] * z["di"]
    dense = 2 * (p["attention"] + p["indexer"] + p["router"]
                 + p["expert"] * pairs_here_per_token)
    fwd = z["layers"] * (dense + scores * causal + attn + target) + 2 * p["head"]
    bwd = 2 * (z["layers"] * (dense + scores * sel + attn) + 2 * p["head"])
    return float(fwd + bwd)
