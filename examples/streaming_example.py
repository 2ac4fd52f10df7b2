"""Streaming training with the native C++ dataplane.

For datasets beyond device memory: rows stream through the C++ batch-assembly
ring (padding/masking/shuffling on a GIL-free thread) while the device trains —
the big-data ingest path that replaces the reference's per-partition Python
loops. With pyspark, feed ``df.rdd.toLocalIterator()``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import sparkflow_tpu.nn as nn
from sparkflow_tpu.graph_utils import build_graph
from sparkflow_tpu.trainer import Trainer


def model():
    x = nn.placeholder([None, 128], name="x")
    y = nn.placeholder([None, 1], name="y")
    h = nn.dense(x, 64, activation="relu")
    nn.sigmoid_cross_entropy(y, nn.dense(h, 1, name="out"))


def row_stream(n_rows=20000, dim=128, seed=0):
    """Simulates an out-of-core source: yields one row at a time."""
    rs = np.random.RandomState(seed)
    w = rs.randn(dim)
    for _ in range(n_rows):
        x = rs.randn(dim).astype(np.float32)
        yield x, float(x @ w > 0)


if __name__ == "__main__":
    smoke = bool(os.environ.get("SPARKFLOW_TPU_SMOKE"))
    tr = Trainer(build_graph(model), "x:0", "y:0", mini_batch_size=256,
                 learning_rate=0.05)
    res = tr.fit_stream(row_stream(n_rows=2000 if smoke else 20000))
    print(f"steps: {len(res.losses)}  loss {res.losses[0]:.3f} -> "
          f"{res.losses[-1]:.3f}  throughput {int(res.examples_per_sec)} rows/s")
