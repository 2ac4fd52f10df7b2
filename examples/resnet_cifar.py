"""ResNet-50 on CIFAR-10-shaped data through the Spark ML pipeline —
BASELINE.md's "ResNet-50 / CIFAR-10" config (a new capability; the reference
has no image-model path at all).

Images travel as flattened 3072-dim vector columns (the Spark-native layout);
the registry spec is the Estimator's graph Param like any other model.
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
    from pyspark.ml.pipeline import Pipeline
else:
    from sparkflow_tpu.localml import (LocalSession as SparkSession,
                                       OneHotEncoder, Pipeline)
    from sparkflow_tpu.localml import Vectors


def synthetic_cifar(spark, n=512):
    rs = np.random.RandomState(0)
    rows = []
    for _ in range(n):
        label = rs.randint(0, 10)
        img = rs.rand(32 * 32 * 3) * (0.5 + 0.05 * label)
        rows.append((float(label), Vectors.dense(img)))
    return spark.createDataFrame(rows, ["label", "features"])


if __name__ == "__main__":
    smoke = bool(os.environ.get("SPARKFLOW_TPU_SMOKE"))
    spark = SparkSession.builder.appName("resnet-cifar").getOrCreate()
    n = 64 if smoke else 2048
    df = synthetic_cifar(spark, n)

    # flattened vector columns reshape to NHWC inside the model; the smoke
    # path shrinks depth/width so the example runs on one CPU core
    spec = build_registry_spec("resnet", num_classes=10,
                               depth=18 if smoke else 50,
                               image_size=32, width=16 if smoke else 64)

    est = SparkAsyncDL(
        inputCol="features",
        tensorflowGraph=spec,
        tfInput="x:0",
        tfLabel="y:0",
        tfOutput="pred:0",
        tfOptimizer="adam",
        tfLearningRate=1e-3,
        iters=1 if smoke else 20,
        miniBatchSize=32 if smoke else 64,
        labelCol="labels",
        predictionCol="predicted")

    pipe = Pipeline(stages=[
        OneHotEncoder(inputCol="label", outputCol="labels", dropLast=False),
        est]).fit(df)
    preds = pipe.transform(df)
    acc = np.mean([float(r["predicted"]) == r["label"] for r in preds.collect()])
    print(f"train accuracy: {acc:.3f}")
