"""End-to-end TEXT pipeline: raw strings -> WordPiece -> transformer.

The reference pipelines start from pre-vectorized features (its examples use
``VectorAssembler``/``OneHotEncoder`` over numeric MNIST columns); it has no
text front-end at all. Here the native C++ WordPiece tokenizer
(``WordpieceEncoder``) turns a string column into fixed-shape token-id and
attention-mask columns, which feed a transformer classifier through
``SparkAsyncDL``'s multi-input path — tokenize / train / predict / pipeline
save+load, all through the standard Spark ML surface.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np

from sparkflow_tpu.compat import USING_PYSPARK
from sparkflow_tpu.models import build_registry_spec
from sparkflow_tpu.tensorflow_async import SparkAsyncDL

if USING_PYSPARK:
    from pyspark.sql import SparkSession
else:
    from sparkflow_tpu.localml import LocalSession as SparkSession
from sparkflow_tpu.localml import OneHotEncoder, WordpieceEncoder

SMOKE = bool(os.environ.get("SPARKFLOW_TPU_SMOKE"))


def synthetic_reviews(n, rs):
    """Tiny sentiment-ish corpus: a marker word decides the label."""
    pos = ["wonderful", "great", "loved", "excellent", "delightful"]
    neg = ["terrible", "awful", "hated", "boring", "dreadful"]
    filler = ["the", "movie", "was", "plot", "acting", "and", "very",
              "with", "scenes", "a", "story"]
    rows = []
    for _ in range(n):
        label = rs.randint(0, 2)
        words = [filler[i] for i in rs.randint(0, len(filler), 8)]
        words.insert(rs.randint(0, len(words)),
                     (pos if label else neg)[rs.randint(0, 5)])
        rows.append((float(label), " ".join(words)))
    return rows


if __name__ == "__main__":
    spark = SparkSession.builder.appName("text-classifier").getOrCreate()
    rs = np.random.RandomState(0)
    seq_len = 16
    df = spark.createDataFrame(synthetic_reviews(200 if SMOKE else 2000, rs),
                               ["label", "text"])

    enc = WordpieceEncoder(inputCol="text", outputCol="tokens",
                           maskCol="mask", maxLen=seq_len)
    oh = OneHotEncoder(inputCol="label", outputCol="labels", dropLast=False)
    encoded = oh.transform(enc.transform(df))

    spec = build_registry_spec(
        "transformer_classifier", vocab_size=len(enc._vocab), num_classes=2,
        hidden=32 if SMOKE else 128, num_layers=2 if SMOKE else 4,
        num_heads=4, mlp_dim=64 if SMOKE else 256, max_len=seq_len,
        dropout=0.1)
    est = SparkAsyncDL(inputCol="tokens", tensorflowGraph=spec,
                       tfInput="input_ids:0", tfLabel="y:0",
                       tfOutput="pred:0", tfOptimizer="adam",
                       tfLearningRate=1e-3, iters=10 if SMOKE else 40,
                       partitions=2, labelCol="labels",
                       predictionCol="predicted", miniBatchSize=32,
                       extraInputCols="mask",
                       extraTfInputs="attention_mask:0")
    model = est.fit(encoded)
    preds = model.transform(encoded)
    acc = np.mean([float(r["predicted"]) == r["label"]
                   for r in preds.collect()])
    print(f"train accuracy: {acc:.3f}")
