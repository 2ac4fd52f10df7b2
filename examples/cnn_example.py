"""MNIST CNN via Pipeline.fit — translation of the reference's
``examples/cnn_example.py``, BASELINE.md's primary configuration. No
benchmark cell runs it: at these widths it fills a thousandth of a chip
(PERF.md section 3)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np

from sparkflow_tpu import nn
from sparkflow_tpu.graph_utils import build_graph
from sparkflow_tpu.tensorflow_async import SparkAsyncDL
from sparkflow_tpu.compat import USING_PYSPARK

if USING_PYSPARK:
    from pyspark.sql import SparkSession
    from pyspark.ml.feature import VectorAssembler, OneHotEncoder
    from pyspark.ml.pipeline import Pipeline
    from pyspark.sql.functions import rand
else:
    from sparkflow_tpu.localml import (LocalSession as SparkSession,
                                       VectorAssembler, OneHotEncoder, Pipeline)
    from sparkflow_tpu.localml.sql import functions
    rand = functions.rand

from simple_dnn import load_df


def cnn_model():
    x = nn.placeholder([None, 784], name='x')
    y = nn.placeholder([None, 10], name='y')
    xr = nn.reshape(x, shape=[-1, 28, 28, 1])
    conv1 = nn.conv2d(xr, 32, 5, activation='relu')
    conv1 = nn.max_pooling2d(conv1, 2, 2)
    conv2 = nn.conv2d(conv1, 64, 3, activation='relu')
    conv2 = nn.max_pooling2d(conv2, 2, 2)
    fc1 = nn.flatten(conv2)
    out = nn.dense(fc1, 10)
    z = nn.argmax(out, 1, name='out')
    loss = nn.softmax_cross_entropy(y, out)
    return loss


if __name__ == '__main__':
    spark = SparkSession.builder \
        .appName("examples") \
        .master('local[4]').config('spark.driver.memory', '4g') \
        .getOrCreate()

    df = load_df(spark)
    mg = build_graph(cnn_model)
    va = VectorAssembler(inputCols=df.columns[1:785], outputCol='features')
    encoded = OneHotEncoder(inputCol='_c0', outputCol='labels', dropLast=False)

    spark_model = SparkAsyncDL(
        inputCol='features',
        tensorflowGraph=mg,
        tfInput='x:0',
        tfLabel='y:0',
        tfOptimizer='adam',
        miniBatchSize=300,
        miniStochasticIters=-1,
        shufflePerIter=True,
        iters=2 if os.environ.get("SPARKFLOW_TPU_SMOKE") else 50,
        partitions=4,
        tfLearningRate=.0001,
        predictionCol='predicted',
        labelCol='labels',
        verbose=1
    )

    p = Pipeline(stages=[va, encoded, spark_model]).fit(df)
    p.write().overwrite().save("cnn")
