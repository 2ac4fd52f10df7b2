"""Online serving: train with Spark, serve over HTTP with micro-batching.

The full path from a fitted estimator to a live endpoint:

1. ``SparkAsyncDL.fit`` trains as usual; the fitted model's ``modelWeights``
   Param is the wire-format weights string.
2. ``InferenceEngine`` loads (graph JSON, weights) and AOT-compiles the apply
   function for a ladder of batch-size buckets — after warmup, no request
   size triggers a compile.
3. ``InferenceServer`` exposes ``/v1/predict`` (micro-batched: concurrent
   requests coalesce into one device call), ``/healthz``, ``/metrics``.
4. ``ServingClient`` hits the endpoint from a pool of threads, then reads the
   serving histograms (batch fill, padding waste, latency p50/p95/p99) back
   from ``/metrics``.
"""

import os
import sys
import threading

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
    x = nn.placeholder([None, 16], name='x')
    y = nn.placeholder([None, 1], name='y')
    h = nn.dense(x, 64, activation='relu')
    out = nn.dense(h, 1, activation='sigmoid', name='outer')
    nn.sigmoid_cross_entropy(y, out)


def main():
    smoke = bool(os.environ.get('SPARKFLOW_TPU_SMOKE'))

    spark = SparkSession.builder.appName('serving-example').getOrCreate()
    rs = np.random.RandomState(0)
    rows = []
    for _ in range(100 if smoke else 400):
        rows.append((1.0, Vectors.dense(rs.normal(0.8, 1.0, 16))))
        rows.append((0.0, Vectors.dense(rs.normal(-0.8, 1.0, 16))))
    df = spark.createDataFrame(rows, ['label', 'features'])

    fitted = SparkAsyncDL(
        inputCol='features', tensorflowGraph=build_graph(model),
        tfInput='x:0', tfLabel='y:0', tfOutput='outer/Sigmoid:0',
        labelCol='label', tfLearningRate=.05, iters=3 if smoke else 15,
        miniBatchSize=128, verbose=0).fit(df)

    # fitted Params -> engine: same graph JSON, same weights wire format
    from sparkflow_tpu.serving import InferenceEngine, InferenceServer, ServingClient
    engine = InferenceEngine(
        fitted.getOrDefault(fitted.modelJson),
        fitted.getOrDefault(fitted.modelWeights),
        input_name='x:0', output_name='outer/Sigmoid:0', max_batch=32)
    print(f'engine ready: buckets={engine.buckets} '
          f'aot_compiles={engine.aot_compiles}')

    with InferenceServer(engine, max_delay_ms=2.0) as server:
        client = ServingClient(server.url)
        print(f'serving at {server.url}  healthz={client.healthz()["status"]}')

        n_clients = 4 if smoke else 16
        hits, lock = [], threading.Lock()

        def one_client(i):
            x = rs.normal(0.8 if i % 2 else -0.8, 1.0, (3, 16))
            pred = client.predict(x)
            correct = np.mean((pred[:, 0] > 0.5) == bool(i % 2))
            with lock:
                hits.append(correct)

        threads = [threading.Thread(target=one_client, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        print(f'{n_clients} concurrent clients served, '
              f'accuracy={np.mean(hits):.3f}')

        m = client.metrics()
        lat = m['histograms']['serving/request_latency_ms']
        fill = m['histograms']['serving/batch_fill_ratio']
        print(f"latency ms p50={lat['p50']:.2f} p95={lat['p95']:.2f} "
              f"p99={lat['p99']:.2f}; mean batch fill={fill['mean']:.3f}")
        print(f'recompiles after warmup: {engine.fallback_compiles}')


if __name__ == '__main__':
    main()
