"""The one general traffic generator. A traffic mix is a data file under
``chipbench/traffic/``; this module turns it and ``--seed`` into the inputs
of a run.

Every seed gives the same *amount and kind* of work: the counts and shapes
are the file's, and the seed draws the contents (and, for a mix with
arrivals, would draw their order and instants; PERF.md section 7 keeps the
design of the open-loop mixes that wait for a serving cell).
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    """``chipbench/traffic/<name>.json``."""
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    if "driver" not in mix:
        raise ValueError(f"{path} names no driver")
    return mix


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, nested groups key by key (how a
    ``rehearse`` block shrinks a file's sizes)."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if (isinstance(v, dict)
                                       and isinstance(out.get(k), dict)) else v
    return out


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """One independent generator per named stream of a run."""
    return np.random.default_rng(
        [int(seed), *(ord(c) for c in stream)])


def train_rows(mix: dict, seed: int, vocab_size: int) -> np.ndarray:
    """``[rows, seq_len]`` token ids from the seed, every row different.
    ``token_law`` says how often each id comes: ``uniform`` (the default),
    or ``zipf`` with its ``exponent`` (id ``i`` with weight
    ``1 / (i + 1) ** exponent``, as the words of a text come)."""
    rng = rng_for(seed, "rows")
    shape = (int(mix["rows"]), int(mix["seq_len"]))
    law = mix.get("token_law", {"law": "uniform"})
    if law["law"] == "uniform":
        ids = rng.integers(0, vocab_size, shape, dtype=np.int64)
    elif law["law"] == "zipf":
        weights = 1.0 / np.arange(1, vocab_size + 1) ** float(law["exponent"])
        ids = rng.choice(vocab_size, shape, p=weights / weights.sum())
    else:
        raise ValueError(f"unknown token_law {law['law']!r}")
    return ids.astype(np.int32)
