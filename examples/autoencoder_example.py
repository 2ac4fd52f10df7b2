"""MNIST dense autoencoder (unsupervised, tfLabel=None) — translation of the
reference's ``examples/autoencoder_example.py``. The bottleneck activations are
read through ``tfOutput='out/Sigmoid:0'`` exactly as in the reference."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sparkflow_tpu import nn
from sparkflow_tpu.graph_utils import build_graph
from sparkflow_tpu.tensorflow_async import SparkAsyncDL
from sparkflow_tpu.compat import USING_PYSPARK

if USING_PYSPARK:
    from pyspark.sql import SparkSession
    from pyspark.ml.feature import VectorAssembler, Normalizer
    from pyspark.sql.functions import rand
else:
    from sparkflow_tpu.localml import (LocalSession as SparkSession,
                                       VectorAssembler, Normalizer)
    from sparkflow_tpu.localml.sql import functions
    rand = functions.rand

from simple_dnn import load_df


def small_model():
    x = nn.placeholder('float', shape=[None, 784], name='x')
    layer1 = nn.dense(x, 256, activation='relu')
    layer2 = nn.dense(layer1, 128, activation='sigmoid', name='out')
    layer3 = nn.dense(layer2, 256, activation='relu')
    layer4 = nn.dense(layer3, 784, activation='sigmoid')
    loss = nn.mean_squared_error(layer4, x)
    return loss


if __name__ == '__main__':
    spark = SparkSession.builder \
        .appName("examples") \
        .master('local[4]').config('spark.driver.memory', '2g') \
        .getOrCreate()

    df = load_df(spark)
    mg = build_graph(small_model)

    va = VectorAssembler(inputCols=df.columns[1:785], outputCol='feats').transform(df).select(['feats'])
    na = Normalizer(inputCol='feats', outputCol='features', p=1.0).transform(va).select(['features'])

    spark_model = SparkAsyncDL(
        inputCol='features',
        tensorflowGraph=mg,
        tfInput='x:0',
        tfLabel=None,
        tfOutput='out/Sigmoid:0',
        tfOptimizer='adam',
        tfLearningRate=.001,
        iters=2 if os.environ.get("SPARKFLOW_TPU_SMOKE") else 10,
        predictionCol='predicted',
        partitions=4,
        miniBatchSize=256,
        verbose=1
    ).fit(na)

    t = spark_model.transform(na).take(1)
    print(t[0]['predicted'])
