# Developer entry points (role parity with the reference's Makefile:1-17,
# which ran the examples and tests in Docker).

.PHONY: test test-fast chip-smoke chip-rehearse test-pyspark docker-test-pyspark examples native clean serve-smoke sim-smoke fleet-smoke chaos-smoke lint-graft lint-graft-strict obs-smoke elastic-smoke decode-smoke spec-smoke tp-smoke pp-smoke zero-smoke race-smoke swap-smoke kvquant-smoke scale-smoke trace-smoke

test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q

test-fast:
	JAX_PLATFORMS=cpu python -m pytest tests/ -x -q -k "not estimator"

# the chip: one process, GPT-2 small widths, fails without a TPU. On the CPU,
# `chip-rehearse` runs the same phases at toy widths.
chip-smoke:
	python chip_smoke.py

chip-rehearse:
	JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 python chip_smoke.py --rehearse --multichip

# real-pyspark e2e: installs pyspark (JVM required) and runs the mirrored
# reference suite on local[2], incl. the StopWordsRemover persistence carrier
test-pyspark:
	pip install "pyspark>=3.4"
	python -m pytest tests/test_pyspark_e2e.py -v

examples:
	cd examples && PYTHONPATH="..:$$PYTHONPATH" SPARKFLOW_TPU_SMOKE=1 python simple_dnn.py && \
	PYTHONPATH="..:$$PYTHONPATH" SPARKFLOW_TPU_SMOKE=1 python cnn_example.py && \
	PYTHONPATH="..:$$PYTHONPATH" SPARKFLOW_TPU_SMOKE=1 python autoencoder_example.py

docker-test-pyspark:
	docker compose run --rm --build test-pyspark

native:
	python -c "from sparkflow_tpu.native.build import load_library; \
	           print('native lib:', load_library(verbose=True))"

clean:
	rm -rf sparkflow_tpu/native/_build .pytest_cache .jax_cache
	find . -name __pycache__ -type d -exec rm -rf {} +

# end-to-end serving smoke: start an InferenceServer on an ephemeral port,
# send one request through ServingClient, assert the prediction shape, stop
serve-smoke:
	PYTHONPATH=".:$$PYTHONPATH" python -c "\
	import numpy as np; \
	import sparkflow_tpu.nn as nn; \
	from sparkflow_tpu.graph_utils import build_graph; \
	from sparkflow_tpu.serving import InferenceEngine, InferenceServer, ServingClient; \
	g = lambda: (lambda x: nn.dense(nn.dense(x, 8, activation='relu'), 2, name='out'))(nn.placeholder([None, 4], name='x')); \
	rs = np.random.RandomState(0); \
	w = [rs.randn(4, 8).astype(np.float32), rs.randn(8).astype(np.float32), rs.randn(8, 2).astype(np.float32), rs.randn(2).astype(np.float32)]; \
	eng = InferenceEngine(build_graph(g), w, input_name='x:0', output_name='out/BiasAdd:0', max_batch=8); \
	srv = InferenceServer(eng, max_delay_ms=1.0).start(); \
	c = ServingClient(srv.url); \
	assert c.healthz()['status'] == 'ok'; \
	p = c.predict(rs.randn(3, 4).tolist()); \
	assert p.shape == (3, 2), p.shape; \
	srv.stop(); \
	print('serve-smoke OK: 3x2 prediction served at', srv.url)"

# fleet chaos smoke: the router test suite, then 3 real replica processes
# behind a RouterServer with a SIGKILL + same-port restart mid-burst —
# zero client-visible failures required (docs/serving.md)
fleet-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_router.py -q
	JAX_PLATFORMS=cpu PYTHONPATH=".:$$PYTHONPATH" python examples/fleet_smoke.py

# decode smoke: the decode test suite, then a real server subprocess
# serving a mixed-length /v1/generate burst — X-Request-Id echoed on every
# response, zero steady-state retraces, clean SIGTERM drain (docs/serving.md)
decode-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_decode.py -q
	JAX_PLATFORMS=cpu PYTHONPATH=".:$$PYTHONPATH" python examples/decode_smoke.py

# speculative-decode smoke: the decode test suite, then a real server
# subprocess with speculation on — a mixed-length greedy burst must be
# token-identical to spec-off decode, zero steady-state retraces, clean
# SIGTERM drain (docs/serving.md)
spec-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_decode.py -q
	JAX_PLATFORMS=cpu PYTHONPATH=".:$$PYTHONPATH" python examples/spec_smoke.py

# tensor-parallel serving smoke: the decode test suite, then a real server
# subprocess hosting a tp=2 mesh-sharded engine (spec decode + prefix cache
# on) — a concurrent mixed-length greedy burst must be token-identical to a
# tp=1 engine, zero steady-state retraces, clean SIGTERM drain
# (docs/serving.md)
tp-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_decode.py -q
	JAX_PLATFORMS=cpu PYTHONPATH=".:$$PYTHONPATH" python examples/tp_smoke.py

# subprocess hosting a pp=2 stage-sharded engine (staged spec decode +
# prefix cache + chunked prefill on) — a concurrent mixed-length greedy
# burst must be token-identical to a pp=1 engine on both staged schedules
# (single-wave and micro-token wave), zero steady-state retraces, clean
# SIGTERM drain (docs/serving.md)
pp-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_decode.py -q
	JAX_PLATFORMS=cpu PYTHONPATH=".:$$PYTHONPATH" python examples/pp_smoke.py

# chaos suite: deterministic fault injection against checkpoints, resume,
# coordinator joins, and serving drain (docs/resilience.md)
chaos-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q

# elastic bounded-staleness DP chaos suite (virtual-time stragglers,
# preemption, lease expiry)
elastic-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py -q

# ZeRO stage sweep: the sharding test suite, then a stage 0->3 parity +
# checkpoint-interchange sweep (docs/sharding.md)
zero-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_zero_sharding.py -q
	JAX_PLATFORMS=cpu PYTHONPATH=".:$$PYTHONPATH" python examples/zero_smoke.py

# graftcheck: sharding / tracing / concurrency lint over the repo's own
# source + the jaxpr self-check over presets x optimizers (docs/analysis.md)
lint-graft:
	JAX_PLATFORMS=cpu python -m sparkflow_tpu.analysis sparkflow_tpu examples

# the CI gate flavor: the same full pass (all GC families, including the
# GC-X6xx resource-lifecycle rules), exits nonzero on ANY finding — this
# is what tests/test_lint_gate.py pins as a tier-1 test
lint-graft-strict:
	JAX_PLATFORMS=cpu python -m sparkflow_tpu.analysis sparkflow_tpu examples --format json
	@echo "lint-graft-strict: clean"

# dynamic race smoke: the decode drain-under-load chaos scenario run
# entirely under the Eraser lockset detector (GC-R402) — zero empty-lockset
# reports required across engine/KV/metrics shared state (docs/analysis.md)
race-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=".:$$PYTHONPATH" python examples/race_smoke.py

# live weight-publication smoke: the weightstore suite (crash-consistent
# publish, hot swap, canary gate, lock/race lints), then a real server
# subprocess hot-swapping weights mid-burst — one good publish (healthz
# version flips exactly once) and one corrupted publish (invisible to
# clients, last-good kept) with zero failures and a clean SIGTERM drain
# (docs/serving.md)
swap-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_weightstore.py -q
	JAX_PLATFORMS=cpu PYTHONPATH=".:$$PYTHONPATH" python examples/swap_smoke.py

# quantized-KV smoke: the int8/fp8 pool battery (kernel dequant parity,
# running-scale appends, churn neutrality, composition parity), a
# real-server int8 smoke (16 concurrent mixed-length greedy generations
# with spec k=3 + prefix cache + chunked prefill, token-identical to
# full-precision decode, healthz advertising the pool layout, clean
# SIGTERM drain) (docs/serving.md)
kvquant-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_kvquant.py -q
	JAX_PLATFORMS=cpu PYTHONPATH=".:$$PYTHONPATH" python examples/kvquant_smoke.py

# elastic autoscaling smoke: the autoscaler test battery (policy units,
# sim step response, live control loop, real-subprocess supervisor), then
# a real 1->3->1 fleet: load step up spawns replicas (zero-compile boot
# from the shared executable store), a SIGKILL mid-burst is reaped and
# replaced within one tick, the trickle phase drains back to min — zero
# client-visible failures throughout (docs/serving.md)
scale-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_autoscaler.py -q
	JAX_PLATFORMS=cpu PYTHONPATH=".:$$PYTHONPATH" python examples/scale_smoke.py

# fleet-simulator smoke: the sim + policy-parity test suites, then the
# 1000-replica x 1M-request what-if with its capacity report
sim-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_sim.py tests/test_policies.py -q
	JAX_PLATFORMS=cpu PYTHONPATH=".:$$PYTHONPATH" python examples/sim_smoke.py

# observability smoke: the spans/stepstats/prometheus/request-tracing suite
# (docs/observability.md)
obs-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_obs.py -q

# distributed-tracing smoke: the tracing test battery (traceparent context,
# cross-process assembly, tail sampling, flight recorder + harvest), then a
# real 2-replica fleet: one hedged /v1/generate assembled into a single
# cross-process waterfall with the hedge loser labeled, and a SIGKILL
# postmortem naming the in-flight trace ids (docs/observability.md)
trace-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_tracing.py -q
	JAX_PLATFORMS=cpu PYTHONPATH=".:$$PYTHONPATH" python examples/trace_smoke.py

# round-2 example additions (text pipeline; TF1 migration needs tensorflow)
examples-extra:
	cd examples && PYTHONPATH="..:$$PYTHONPATH" SPARKFLOW_TPU_SMOKE=1 python text_classifier.py && \
	PYTHONPATH="..:$$PYTHONPATH" SPARKFLOW_TPU_SMOKE=1 python bert_classifier.py && \
	PYTHONPATH="..:$$PYTHONPATH" SPARKFLOW_TPU_SMOKE=1 python tf1_migration.py && \
	PYTHONPATH="..:$$PYTHONPATH" SPARKFLOW_TPU_SMOKE=1 python rnn_sequence.py
