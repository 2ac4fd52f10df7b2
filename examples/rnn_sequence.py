"""Recurrent models end-to-end: a bi-GRU text classifier and an LSTM LM.

The reference has no sequence models (SURVEY.md §5); this example shows the
``rnn_classifier`` / ``rnn_lm`` registry family driving the same Spark ML
surface as every other model: tokenize -> fit -> transform -> evaluate, and
a character LM trained with ``Trainer`` directly. The recurrence compiles to
one ``lax.scan`` per layer with a single fused gate GEMM per step — the
TPU-idiomatic shape for ``tf.nn.dynamic_rnn``-era models.
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
from sparkflow_tpu.localml import (BinaryClassificationEvaluator, Pipeline,
                                   WordpieceEncoder)

SMOKE = bool(os.environ.get("SPARKFLOW_TPU_SMOKE"))


def synthetic_reviews(n, rs):
    pos_words = ["great", "wonderful", "loved", "superb", "delight"]
    neg_words = ["terrible", "awful", "hated", "dreadful", "boring"]
    filler = ["the", "movie", "plot", "acting", "was", "a", "bit", "film"]
    rows = []
    for _ in range(n):
        label = int(rs.rand() > 0.5)
        words = list(rs.choice(filler, rs.randint(4, 9)))
        words.insert(rs.randint(0, len(words)),
                     str(rs.choice(pos_words if label else neg_words)))
        rows.append((" ".join(words), float(label)))
    return rows


def classifier_pipeline(spark, rs):
    max_len = 16
    df = spark.createDataFrame(synthetic_reviews(60 if SMOKE else 400, rs),
                               ["text", "label"])
    spec = build_registry_spec(
        "rnn_classifier", vocab_size=256, num_classes=2, hidden=32,
        num_layers=1, max_len=max_len, cell="gru", bidirectional=True)
    pipe = Pipeline(stages=[
        WordpieceEncoder(inputCol="text", outputCol="ids", maskCol="mask",
                         maxLen=max_len),
        SparkAsyncDL(inputCol="ids", tensorflowGraph=spec,
                     tfInput="input_ids:0", tfLabel="y:0", labelCol="label",
                     tfOutput="probs:0", extraInputCols="mask",
                     extraTfInputs="attention_mask:0",
                     iters=10 if SMOKE else 60, miniBatchSize=32,
                     tfOptimizer="adam", tfLearningRate=1e-2,
                     predictionCol="rawPrediction"),
    ])
    model = pipe.fit(df)
    scored = model.transform(df)
    auc = BinaryClassificationEvaluator(labelCol="label").evaluate(scored)
    print(f"bi-GRU classifier train AUC: {auc:.3f}")
    return auc


def char_lm(rs):
    """LSTM character LM on a toy corpus via the Trainer directly."""
    from sparkflow_tpu.trainer import Trainer

    text = ("the quick brown fox jumps over the lazy dog " * 40)
    chars = sorted(set(text))
    idx = {c: i for i, c in enumerate(chars)}
    seq = 32
    ids = np.array([idx[c] for c in text], np.float32)
    n = (len(ids) - 1) // seq
    X = ids[:n * seq].reshape(n, seq)

    spec = build_registry_spec("rnn_lm", vocab_size=len(chars), hidden=64,
                               num_layers=2, max_len=seq, cell="lstm")
    tr = Trainer(spec, "input_ids:0", None, optimizer="adam",
                 learning_rate=5e-3, iters=5 if SMOKE else 40,
                 mini_batch_size=16)
    res = tr.fit(X, None)
    ppl0, ppl1 = np.exp(res.losses[0]), np.exp(res.losses[-1])
    print(f"LSTM char-LM perplexity: {ppl0:.1f} -> {ppl1:.1f}")
    return ppl1


if __name__ == "__main__":
    rs = np.random.RandomState(0)
    spark = SparkSession.builder.appName("rnn-example").getOrCreate()
    auc = classifier_pipeline(spark, rs)
    ppl = char_lm(rs)
    if not SMOKE:
        assert auc > 0.9, auc
        assert ppl < 10.0, ppl
    print("rnn_sequence example OK")
