"""Zero-rewrite migration: run a sparkflow TF1 model on sparkflow-tpu.

The reference serializes models as MetaGraphDef JSON
(``sparkflow/graph_utils.py:6-15``) and ships TF1 Saver checkpoints
(``sparkflow/tensorflow_model_loader.py``). Both work here UNCHANGED:

1. a TF1 ``build_graph`` JSON string trains via ``SparkAsyncDL`` directly
   (interpreted node-by-node in JAX — no TensorFlow at execution time);
2. a Saver checkpoint directory becomes a serving model via
   ``load_tensorflow_model`` with no graph rebuild (the checkpoint's own
   ``.meta`` is the serving graph).

Generating the TF1 artifacts below needs TensorFlow installed (it is only
used to CREATE the fixtures, mimicking a legacy sparkflow user's assets).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np

SMOKE = bool(os.environ.get("SPARKFLOW_TPU_SMOKE"))


def make_legacy_artifacts(tmp="/tmp/sparkflow_tf1_demo"):
    """What an existing sparkflow user already has: a metagraph JSON and a
    trained TF1 Saver checkpoint."""
    import tensorflow as tf
    from google.protobuf import json_format
    tf1 = tf.compat.v1
    tf1.disable_eager_execution()

    def dense(x, units, name, act=None):
        with tf1.variable_scope(name):
            k = tf1.get_variable("kernel", [int(x.shape[-1]), units],
                                 initializer=tf1.glorot_uniform_initializer())
            b = tf1.get_variable("bias", [units],
                                 initializer=tf1.zeros_initializer())
        y = tf1.nn.bias_add(tf1.matmul(x, k), b)
        return act(y) if act else y

    os.makedirs(tmp, exist_ok=True)
    g = tf1.Graph()
    with g.as_default():
        x = tf1.placeholder(tf.float32, [None, 2], name="x")
        y = tf1.placeholder(tf.float32, [None, 1], name="y")
        h = dense(x, 12, "d1", tf.nn.relu)
        out = tf1.sigmoid(dense(h, 1, "outer"), name="out_act")
        tf1.losses.log_loss(y, out)
        mg_json = json_format.MessageToJson(tf1.train.export_meta_graph())
        prefix = os.path.join(tmp, "to_load")
        with tf1.Session(graph=g) as sess:
            sess.run(tf1.global_variables_initializer())
            tf1.train.Saver().save(sess, prefix)
    return mg_json, prefix


if __name__ == "__main__":
    from sparkflow_tpu.compat import USING_PYSPARK
    if USING_PYSPARK:
        from pyspark.sql import SparkSession
        from pyspark.ml.linalg import Vectors
    else:
        from sparkflow_tpu.localml import (LocalSession as SparkSession,
                                           Vectors)
    from sparkflow_tpu.model_loader import load_tensorflow_model
    from sparkflow_tpu.tensorflow_async import SparkAsyncDL

    mg_json, ckpt_prefix = make_legacy_artifacts()
    spark = SparkSession.builder.appName("tf1-migration").getOrCreate()
    rs = np.random.RandomState(0)
    rows = ([(1.0, Vectors.dense(rs.normal(2, 1, 2))) for _ in range(150)]
            + [(0.0, Vectors.dense(rs.normal(-2, 1, 2))) for _ in range(150)])
    df = spark.createDataFrame(rows, ["label", "features"])

    # 1) the reference's build_graph JSON trains as-is
    est = SparkAsyncDL(inputCol="features", tensorflowGraph=mg_json,
                       tfInput="x:0", tfLabel="y:0", tfOutput="out_act:0",
                       tfOptimizer="adam", tfLearningRate=0.1,
                       iters=5 if SMOKE else 25, partitions=2,
                       labelCol="label", predictionCol="predicted",
                       miniBatchSize=64)
    model = est.fit(df)
    errs = sum(1 for r in model.transform(df).collect()
               if round(float(r["predicted"])) != float(r["label"]))
    print(f"trained from raw MetaGraphDef JSON: {errs}/300 errors")

    # 2) the Saver checkpoint serves without a rebuilt graph
    served = load_tensorflow_model(ckpt_prefix, "features", "x:0",
                                   "out_act:0")
    n = served.transform(df).count()
    print(f"served {n} rows from the TF1 checkpoint's own .meta graph")
