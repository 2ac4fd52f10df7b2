"""The generator of the block-diffusion mixes (``traffic/blockdiff-*.json``),
beside ``traffic.py``: a training row is its clean ids and then their noised
copy, so a mix of ``rows`` rows of ``seq_len`` tokens gives ``[rows, 2 *
seq_len]`` ids.

The clean ids are ``traffic.train_rows``' (the mix's ``token_law``, the
stream ``rows``). The noise is drawn with the row from ``--seed``, a stream
of its own: every block of ``block_length`` positions masks ``k`` of them,
``k`` uniform on ``1 .. block_length``, the positions uniform among the
``C(block_length, k)`` choices; a masked position holds the configuration's
``mask_token_id``. Every seed gives the same amount of work: ``seq_len /
block_length`` blocks a row, ``(block_length + 1) / (2 block_length)`` of the
positions masked in expectation. The law is the library's
(``sparkflow_tpu.models.noise_rows``); nothing is imported from it.
"""

from __future__ import annotations

import numpy as np

from chipbench import traffic


def masked_positions(mix: dict, seed: int) -> np.ndarray:
    """``bool [rows, seq_len]``: the positions each row's noise masks."""
    rows, length = int(mix["rows"]), int(mix["seq_len"])
    block = int(mix["noise"]["block_length"])
    if length % block:
        raise ValueError(f"blocks of {block} do not divide {length} tokens")
    rng = traffic.rng_for(seed, mix["noise"].get("stream", "noise"))
    shape = (rows, length // block, block)
    k = rng.integers(1, block + 1, shape[:2])
    # the k positions of smallest rank among a block's uniform draws
    rank = np.argsort(np.argsort(rng.random(shape), axis=-1), axis=-1)
    return (rank < k[..., None]).reshape(rows, length)


def noised_rows(mix: dict, seed: int, cfg: dict) -> np.ndarray:
    """``int32 [rows, 2 * seq_len]``: each row's clean ids from the held
    slice, then their noised copy."""
    if int(mix["noise"]["block_length"]) != int(cfg["block_length"]):
        raise ValueError(
            f"the mix noises blocks of {mix['noise']['block_length']}, the "
            f"configuration attends blocks of {cfg['block_length']}")
    clean = traffic.train_rows(mix, seed, cfg["vocab_size"])
    noised = np.where(masked_positions(mix, seed),
                      np.int32(cfg["mask_token_id"]), clean)
    return np.concatenate([clean, noised], axis=1).astype(np.int32)
