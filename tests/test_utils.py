"""Native dataplane (queue + CSV), streaming fit, metrics, tracing."""

import os
import threading

import numpy as np
import pytest

import sparkflow_tpu.nn as nn
from sparkflow_tpu.graph_utils import build_graph
from sparkflow_tpu.trainer import Trainer
from sparkflow_tpu.utils.data import BatchQueue, load_csv_matrix
from sparkflow_tpu.utils.metrics import Metrics, timer
from sparkflow_tpu.native.build import load_library


def test_native_library_builds():
    # the image ships g++; if this fails the numpy fallback still works but we
    # want to know the native path regressed
    assert load_library() is not None


def test_csv_loader_roundtrip(tmp_path):
    rs = np.random.RandomState(0)
    m = rs.rand(50, 7).astype(np.float32)
    p = str(tmp_path / "m.csv")
    np.savetxt(p, m, delimiter=",", fmt="%.6f")
    a = load_csv_matrix(p)
    assert a.shape == (50, 7)
    np.testing.assert_allclose(a, m, atol=1e-5)


@pytest.mark.parametrize("native", [True, False], ids=["native", "python"])
def test_batch_queue_preserves_rows_and_masks(native, monkeypatch):
    """Every pushed row comes out exactly once, through the C++ ring and
    through the python queue it falls back to without a toolchain."""
    if not native:
        monkeypatch.setattr("sparkflow_tpu.utils.data.load_library",
                            lambda: None)
    rs = np.random.RandomState(1)
    M = rs.rand(250, 5).astype(np.float32)
    Y = rs.rand(250, 2).astype(np.float32)
    q = BatchQueue(batch_size=64, row_dim=5, label_dim=2, capacity=3,
                   shuffle=True, seed=7)
    assert (q._lib is not None) == native

    def produce():
        for i in range(0, 250, 90):
            q.push(M[i:i + 90], Y[i:i + 90])
        q.finish()

    threading.Thread(target=produce, daemon=True).start()
    rows, total = [], 0
    for x, y, mask, n in q:
        assert x.shape == (64, 5) and mask.sum() == n
        assert np.all(x[n:] == 0)  # padding is zeroed
        rows.append(x[:n])
        total += n
    q.close()
    assert total == 250
    got = np.concatenate(rows)
    np.testing.assert_allclose(np.sort(got[:, 0]), np.sort(M[:, 0]), atol=1e-6)


def test_batch_queue_unsupervised():
    q = BatchQueue(batch_size=16, row_dim=3, label_dim=0, capacity=2,
                   shuffle=False)
    q.push(np.ones((10, 3), np.float32))
    q.finish()
    x, y, mask, n = q.pop()
    assert n == 10 and y.shape[1] == 0
    assert q.pop() is None
    q.close()


def test_fit_stream_learns():
    rs = np.random.RandomState(0)
    M = rs.randn(600, 12).astype(np.float32)
    lbl = (M @ rs.randn(12) > 0).astype(np.float32)

    def m():
        x = nn.placeholder([None, 12], name="x")
        y = nn.placeholder([None, 1], name="y")
        nn.sigmoid_cross_entropy(y, nn.dense(x, 1, name="out"))

    tr = Trainer(build_graph(m), "x:0", "y:0", mini_batch_size=64,
                 learning_rate=0.2)
    res = tr.fit_stream(zip(list(M), list(lbl)))
    assert res.losses[-1] < res.losses[0]
    assert len(res.losses) == -(-600 // 64)


def test_metrics_registry():
    m = Metrics()
    for i in range(5):
        m.scalar("loss", 1.0 / (i + 1), step=i)
    m.incr("steps", 5)
    with timer("fake", m):
        pass
    s = m.summary()
    assert s["loss"]["count"] == 5 and s["loss"]["last"] == 0.2
    assert s["counters"]["steps"] == 5
    assert "time/fake" in s


def test_metrics_jsonl_dump(tmp_path):
    m = Metrics()
    m.scalar("a", 1.0)
    p = str(tmp_path / "m.jsonl")
    m.dump_jsonl(p)
    import json
    lines = [json.loads(l) for l in open(p)]
    assert lines[0]["name"] == "a"


def test_metrics_histogram_percentiles():
    m = Metrics()
    for v in range(1, 101):  # 1..100: pN is ~N at 1% granularity
        m.observe("latency_ms", float(v))
    assert m.percentile("latency_ms", 0) == 1.0
    assert m.percentile("latency_ms", 100) == 100.0
    assert m.percentile("latency_ms", 50) == pytest.approx(50.5)
    ps = m.percentiles("latency_ms")
    assert set(ps) == {"p50", "p95", "p99"}
    assert ps["p95"] == pytest.approx(95.05)
    assert ps["p99"] == pytest.approx(99.01)
    assert ps["p50"] <= ps["p95"] <= ps["p99"]
    h = m.histograms()["latency_ms"]
    assert h["count"] == 100 and h["min"] == 1.0 and h["max"] == 100.0
    assert h["mean"] == pytest.approx(50.5)
    with pytest.raises(KeyError):
        m.percentile("nope", 50)
    from sparkflow_tpu.utils.metrics import _Histogram
    with pytest.raises(ValueError):
        _Histogram().percentile(50)  # empty histogram


def test_metrics_histogram_reservoir_bounded():
    from sparkflow_tpu.utils.metrics import HISTOGRAM_RESERVOIR
    m = Metrics()
    n = HISTOGRAM_RESERVOIR * 3
    for v in range(n):
        m.observe("big", float(v))
    h = m._hists["big"]
    assert len(h.samples) == HISTOGRAM_RESERVOIR  # memory stays bounded
    s = m.histograms()["big"]
    assert s["count"] == n  # exact stats survive the sampling
    assert s["min"] == 0.0 and s["max"] == float(n - 1)
    # reservoir-sampled median of a uniform ramp lands near the true median
    assert abs(m.percentile("big", 50) - (n - 1) / 2) < n * 0.05


def test_metrics_histogram_concurrent_observe():
    m = Metrics()

    def worker(k):
        for v in range(200):
            m.observe("shared", float(v + k))

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert m._hists["shared"].count == 8 * 200


def test_metrics_histogram_in_summary_and_jsonl(tmp_path):
    m = Metrics()
    assert "histograms" not in m.summary()  # only present once observed
    m.observe("h", 2.0)
    m.observe("h", 4.0)
    s = m.summary()
    assert s["histograms"]["h"]["count"] == 2
    p = str(tmp_path / "m.jsonl")
    m.dump_jsonl(p)
    import json
    hist_lines = [json.loads(l) for l in open(p) if "histogram" in l]
    assert hist_lines and hist_lines[0]["name"] == "h"
    assert hist_lines[0]["histogram"]["mean"] == pytest.approx(3.0)
    m.reset()
    assert m.histograms() == {}


def test_tracing_annotate_runs():
    import jax
    import jax.numpy as jnp
    from sparkflow_tpu.utils.tracing import annotate

    with annotate("test-region"):
        v = jax.jit(lambda x: x * 2)(jnp.ones(4))
    assert float(v.sum()) == 8.0


def test_reference_import_paths():
    """Every module path a reference user imports exists here with the same
    public symbols (swap `sparkflow` -> `sparkflow_tpu` and code ports):
    tensorflow_async, tensorflow_model_loader, HogwildSparkModel, RWLock,
    ml_util, graph_utils, pipeline_util (reference tree listing)."""
    from sparkflow_tpu.tensorflow_async import SparkAsyncDL, SparkAsyncDLModel
    from sparkflow_tpu.tensorflow_model_loader import (
        attach_tensorflow_model_to_pipeline, load_tensorflow_model)
    from sparkflow_tpu.HogwildSparkModel import HogwildSparkModel
    from sparkflow_tpu.RWLock import RWLock
    from sparkflow_tpu.ml_util import (convert_json_to_weights,
                                       convert_weights_to_json, predict_func)
    from sparkflow_tpu.graph_utils import build_adam_config, build_graph
    from sparkflow_tpu.pipeline_util import (PysparkPipelineWrapper,
                                             PysparkReaderWriter)
    for sym in (SparkAsyncDL, SparkAsyncDLModel, load_tensorflow_model,
                attach_tensorflow_model_to_pipeline, HogwildSparkModel,
                RWLock, predict_func, convert_weights_to_json,
                convert_json_to_weights, build_graph, build_adam_config,
                PysparkPipelineWrapper, PysparkReaderWriter):
        assert callable(sym) or isinstance(sym, type)
