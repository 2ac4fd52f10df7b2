"""MNIST MLP via Pipeline.fit — translation of the reference's
``examples/simple_dnn.py`` to the TPU-native framework.

The model function ports line-for-line from TF1 to :mod:`sparkflow_tpu.nn`;
the Estimator params are identical. With pyspark installed this uses the real
SparkSession; standalone it runs on localml. MNIST csv is loaded if present
(same path the reference expects), else a synthetic stand-in is generated so
the example always runs.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np

from sparkflow_tpu import nn
from sparkflow_tpu.graph_utils import build_adam_config, build_graph
from sparkflow_tpu.tensorflow_async import SparkAsyncDL
from sparkflow_tpu.pipeline_util import PysparkPipelineWrapper
from sparkflow_tpu.compat import USING_PYSPARK

if USING_PYSPARK:
    from pyspark.sql import SparkSession
    from pyspark.ml.feature import VectorAssembler, OneHotEncoder
    from pyspark.ml.evaluation import MulticlassClassificationEvaluator
    from pyspark.ml.pipeline import Pipeline, PipelineModel
    from pyspark.sql.functions import rand
else:
    from sparkflow_tpu.localml import (LocalSession as SparkSession,
                                       VectorAssembler, OneHotEncoder,
                                       MulticlassClassificationEvaluator,
                                       Pipeline, PipelineModel)
    from sparkflow_tpu.localml.sql import functions
    rand = functions.rand


def small_model():
    x = nn.placeholder([None, 784], name='x')
    y = nn.placeholder([None, 10], name='y')
    layer1 = nn.dense(x, 256, activation='relu', kernel_initializer='glorot_uniform')
    layer2 = nn.dense(layer1, 256, activation='relu', kernel_initializer='glorot_uniform')
    out = nn.dense(layer2, 10, kernel_initializer='glorot_uniform')
    z = nn.argmax(out, 1, name='out')
    loss = nn.softmax_cross_entropy(y, out)
    return loss


def load_df(spark, n_synth=4096):
    if os.environ.get("SPARKFLOW_TPU_SMOKE"):  # fast CI/smoke path
        n_synth = 512
    path = os.path.join(os.path.dirname(__file__), 'mnist_train.csv')
    if os.path.exists(path):
        return spark.read.option("inferSchema", "true").csv(path).orderBy(rand())
    rs = np.random.RandomState(0)
    rows = []
    for _ in range(n_synth):
        label = rs.randint(0, 10)
        px = rs.rand(784) * (0.3 + 0.07 * label)
        rows.append(tuple([int(label)] + px.tolist()))
    cols = [f"_c{i}" for i in range(785)]
    return spark.createDataFrame(rows, cols).orderBy(rand())


if __name__ == '__main__':
    spark = SparkSession.builder \
        .appName("examples") \
        .master('local[4]').config('spark.driver.memory', '2g') \
        .getOrCreate()

    df = load_df(spark)
    mg = build_graph(small_model)
    adam_config = build_adam_config(learning_rate=0.001, beta1=0.9, beta2=0.999)

    vector_assembler = VectorAssembler(inputCols=df.columns[1:785], outputCol='features')
    encoder = OneHotEncoder(inputCol='_c0', outputCol='labels', dropLast=False)

    spark_model = SparkAsyncDL(
        inputCol='features',
        tensorflowGraph=mg,
        tfInput='x:0',
        tfLabel='y:0',
        tfOutput='out:0',
        tfOptimizer='adam',
        miniBatchSize=300,
        miniStochasticIters=1,
        shufflePerIter=True,
        iters=50,
        predictionCol='predicted',
        labelCol='labels',
        partitions=4,
        verbose=1,
        optimizerOptions=adam_config
    )

    p = Pipeline(stages=[vector_assembler, encoder, spark_model]).fit(df)
    p.write().overwrite().save('simple_dnn')

    loaded_pipeline = PysparkPipelineWrapper.unwrap(PipelineModel.load('simple_dnn'))

    predictions = loaded_pipeline.transform(df)
    evaluator = MulticlassClassificationEvaluator(
        labelCol="_c0", predictionCol="predicted", metricName="accuracy")
    accuracy = evaluator.evaluate(predictions)
    print("Test Error = %g" % (1.0 - accuracy))
