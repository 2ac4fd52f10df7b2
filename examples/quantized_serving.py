"""int8 quantized inference: train full-precision, serve int8.

A TPU-era capability beyond the reference (which serves f32 through
``tf.Session``, ``sparkflow/ml_util.py:65-73``): after a normal fit, flip
``inferenceQuantize`` on the fitted model and ``transform`` serves
symmetric per-channel int8 weights —

- ``weight_only``: kernels stored int8, dequantized at the matmul; halves
  weight HBM traffic vs bf16 (4x vs f32) with accuracy loss bounded by
  8-bit weight rounding. The default choice for bandwidth-bound serving.
- ``dynamic``: activations also quantized per-row at runtime and the
  matmul runs int8 x int8 -> int32 on the MXU's int8 path (2x the bf16
  peak on a v5e).

The persisted pipeline keeps full-precision weights; quantization happens
executor-side at serve time, cached per (weights, mode).
"""

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
    from pyspark.ml.linalg import Vectors
else:
    from sparkflow_tpu.localml import LocalSession as SparkSession, Vectors


def model():
    x = nn.placeholder([None, 32], name='x')
    y = nn.placeholder([None, 1], name='y')
    h = nn.dense(x, 256, activation='relu')
    h = nn.dense(h, 256, activation='relu')
    out = nn.dense(h, 1, activation='sigmoid', name='outer')
    nn.sigmoid_cross_entropy(y, out)


def main():
    spark = SparkSession.builder.appName('quantized-serving').getOrCreate()
    rs = np.random.RandomState(0)
    rows = []
    for _ in range(100 if os.environ.get('SPARKFLOW_TPU_SMOKE') else 500):
        rows.append((1.0, Vectors.dense(rs.normal(0.8, 1.0, 32))))
        rows.append((0.0, Vectors.dense(rs.normal(-0.8, 1.0, 32))))
    df = spark.createDataFrame(rows, ['label', 'features'])

    fitted = SparkAsyncDL(
        inputCol='features', tensorflowGraph=build_graph(model),
        tfInput='x:0', tfLabel='y:0', tfOutput='outer/Sigmoid:0',
        labelCol='label', tfLearningRate=.05, iters=3 if os.environ.get('SPARKFLOW_TPU_SMOKE') else 15, miniBatchSize=128,
        verbose=1).fit(df)

    def error_rate(m):
        preds = m.transform(df).collect()
        return np.mean([round(float(r['predicted'])) != float(r['label'])
                        for r in preds])

    base = error_rate(fitted)
    print(f'f32 serving error rate:        {base:.4f}')
    for mode in ('weight_only', 'dynamic'):
        fitted.setParams(inferenceQuantize=mode)
        print(f'{mode:12s} serving error rate: {error_rate(fitted):.4f}')


if __name__ == '__main__':
    main()
