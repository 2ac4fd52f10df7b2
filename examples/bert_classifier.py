"""BERT-base text classification at seq-len 512 — BASELINE.md's transformer
config (new capability; the reference has no sequence models).

Token ids travel as a Spark vector column; with real pyspark, tokenize with
Spark ML (`Tokenizer` + a vocab map) upstream — here synthetic ids keep the
example self-contained. On TPU this runs bf16 with the pallas flash-attention
kernel; CPU smoke mode shrinks the model.

Round-4 surfaces: set ``SPARKFLOW_TPU_MESH="dp=2,tp=4"`` to train the same
fit tensor-parallel from the Param surface (the sharded jit keeps the
pallas kernel via a nested shard_map), and the fitted model also serves an
int8-quantized transform for comparison.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np

from sparkflow_tpu.models import build_registry_spec
from sparkflow_tpu.tensorflow_async import SparkAsyncDL
from sparkflow_tpu.compat import USING_PYSPARK

if USING_PYSPARK:
    from pyspark.sql import SparkSession
    from pyspark.ml.feature import OneHotEncoder
    from pyspark.ml.linalg import Vectors
    from pyspark.ml.pipeline import Pipeline
else:
    from sparkflow_tpu.localml import (LocalSession as SparkSession,
                                       OneHotEncoder, Pipeline, Vectors)

SMOKE = bool(os.environ.get("SPARKFLOW_TPU_SMOKE"))


def synthetic_text(spark, n, seq_len, vocab):
    rs = np.random.RandomState(0)
    rows = []
    for _ in range(n):
        label = rs.randint(0, 2)
        ids = rs.randint(10, vocab, seq_len)
        if label:
            ids[:: 7] = 3  # a "positive" marker token pattern
        # variable-length documents: real tokens then padding, with the
        # attention mask travelling as its own column
        n_real = rs.randint(seq_len // 2, seq_len + 1)
        mask = np.zeros(seq_len)
        mask[:n_real] = 1.0
        ids[n_real:] = 0
        rows.append((float(label), Vectors.dense(ids.astype(float)),
                     Vectors.dense(mask)))
    return spark.createDataFrame(rows, ["label", "tokens", "mask"])


if __name__ == "__main__":
    spark = SparkSession.builder.appName("bert-classifier").getOrCreate()
    seq_len = 64 if SMOKE else 512
    vocab = 1000 if SMOKE else 30522
    df = synthetic_text(spark, 256 if SMOKE else 4096, seq_len, vocab)

    spec = build_registry_spec(
        "transformer_classifier",
        vocab_size=vocab, num_classes=2,
        hidden=64 if SMOKE else 768,
        num_layers=2 if SMOKE else 12,
        num_heads=4 if SMOKE else 12,
        mlp_dim=128 if SMOKE else 3072,
        max_len=seq_len, dropout=0.1)

    est = SparkAsyncDL(
        inputCol="tokens",
        tensorflowGraph=spec,
        tfInput="input_ids:0",
        tfLabel="y:0",
        tfOutput="pred:0",
        tfOptimizer="adam",
        tfLearningRate=3e-4,
        iters=3 if SMOKE else 10,
        miniBatchSize=32,
        labelCol="labels",
        predictionCol="predicted",
        # multi-input feed: the attention mask rides a second column into a
        # second graph tensor (train AND transform)
        extraInputCols="mask",
        extraTfInputs="attention_mask:0",
        # optional multi-device mesh from the env (e.g. "dp=2,tp=4"); tp
        # uses the model's megatron rules, and attention keeps the pallas
        # kernel per shard
        **({"meshShape": os.environ["SPARKFLOW_TPU_MESH"]}
           if os.environ.get("SPARKFLOW_TPU_MESH") else {}))

    pipe = Pipeline(stages=[
        OneHotEncoder(inputCol="label", outputCol="labels", dropLast=False),
        est]).fit(df)
    preds = pipe.transform(df)
    acc = np.mean([float(r["predicted"]) == r["label"] for r in preds.collect()])
    print(f"train accuracy: {acc:.3f}")

    # int8 serving: same fitted model, weights quantized executor-side
    pipe.stages[-1].setParams(inferenceQuantize="weight_only")
    qpreds = pipe.transform(df)
    qacc = np.mean([float(r["predicted"]) == r["label"]
                    for r in qpreds.collect()])
    print(f"int8 (weight_only) serving accuracy: {qacc:.3f}")
