"""Race-detection smoke: decode drain-under-load beneath the Eraser lockset
detector (``make race-smoke``).

The scenario is the decode plane's hardest concurrency case — a
:class:`ContinuousBatcher` worker admitting/stepping/retiring against a
:class:`DecodeEngine` + :class:`PagedKVCache` while client threads submit
generations and a drain lands mid-burst — run entirely in-process with a
:class:`~sparkflow_tpu.analysis.racecheck.RaceTracker` installed:

1. build a tiny transformer ``DecodeEngine`` and wrap its lock, the KV
   pool's lock, and the metrics lock in ``InstrumentedLock``; put the
   engine/KV counters under lockset tracking (before the batcher spawns
   its worker thread, so every thread only ever sees the wrappers);
2. drive a concurrent burst of mixed-budget ``submit()`` calls from
   several client threads;
3. ``begin_drain()`` mid-burst — in-flight generations must finish, late
   submissions must be refused with :class:`Draining`;
4. assert every accepted future resolved, then **assert the tracker saw
   zero empty-lockset fields** — any unguarded cross-thread access in the
   admit/step/retire/drain protocol fails the smoke with all three stacks.

Runs on CPU (``JAX_PLATFORMS=cpu``) in well under a minute.
"""

import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from sparkflow_tpu.analysis import racecheck, restrack
from sparkflow_tpu.models.registry import build_registry_spec, model_from_json
from sparkflow_tpu.serving import ContinuousBatcher, DecodeEngine, Draining

VOCAB = 97
WORKERS = 4
REQUESTS_PER_WORKER = 4


def make_engine() -> DecodeEngine:
    spec = build_registry_spec("transformer_lm", vocab_size=VOCAB, hidden=32,
                               num_layers=2, num_heads=4, mlp_dim=64,
                               max_len=64, dropout=0.0)
    model = model_from_json(spec)
    params = model.init(jax.random.PRNGKey(0))
    return DecodeEngine(model, params, num_slots=4, page_size=8, seed=0,
                        prefill_chunk=8)


def main() -> None:
    tracker = racecheck.RaceTracker().install()
    engine = make_engine()
    # instrument BEFORE the batcher starts its worker thread: every thread
    # in the run then acquires only the wrapped locks, so held locksets
    # are complete
    racecheck.instrument_object(
        engine, fields=("_steps", "_tokens_out", "_prefills"),
        name="DecodeEngine")
    racecheck.instrument_object(
        engine.kv, fields=("_prefix_lookups", "_prefix_hits",
                           "_tokens_saved"),
        name="PagedKVCache")
    racecheck.instrument_object(engine.metrics, name="Metrics")
    # SPARKFLOW_TPU_RESTRACK=1 additionally audits resource balance: every
    # decode slot prefill() checks out must come back through release() by
    # the end of the drain, or the leak's acquisition stack fails the smoke
    retracker = restrack.ResourceTracker().install() \
        if restrack.enabled() else None
    if retracker is not None:
        restrack.instrument_engine(engine)
    batcher = ContinuousBatcher(engine, max_queue=64)
    if retracker is not None:
        restrack.instrument_batcher(batcher)

    futures, refused = [], []
    fut_mu = threading.Lock()

    def client(k: int) -> None:
        for j in range(REQUESTS_PER_WORKER):
            prompt = [(7 * k + j) % VOCAB, (3 + j) % VOCAB, 11]
            try:
                f = batcher.submit(prompt,
                                   max_new_tokens=4 + 3 * (j % 3),
                                   request_id=f"race-{k}-{j}")
                with fut_mu:
                    futures.append(f)
            except Draining:
                with fut_mu:
                    refused.append((k, j))
            time.sleep(0.01)

    threads = [threading.Thread(target=client, args=(k,), name=f"client-{k}")
               for k in range(WORKERS)]
    for t in threads:
        t.start()

    # chaos: drain while the burst is still submitting and slots are live
    time.sleep(0.15)
    batcher.begin_drain()
    try:
        batcher.submit([1, 2, 3], max_new_tokens=2)
        raise AssertionError("post-drain submit was accepted")
    except Draining:
        refused.append(("post-drain", 0))
    for t in threads:
        t.join()
    assert batcher.wait_drained(timeout=60.0), "drain did not complete"
    batcher.close()
    tracker.uninstall()

    for f in futures:  # every accepted request must have finished cleanly
        out = f.result(timeout=60.0)
        assert out["num_tokens"] == len(out["tokens"]) > 0, out

    tracker.assert_clean()
    restrack_note = ""
    if retracker is not None:
        retracker.uninstall()
        retracker.assert_balanced()
        restrack_note = (f" and zero unbalanced resources "
                         f"({retracker.acquired} acquired, "
                         f"{retracker.released} released)")
    print(f"race-smoke OK: {len(futures)} generations "
          f"({len(refused)} refused post-drain) through drain-under-load "
          f"with zero empty-lockset reports over "
          f"{len(tracker._fields)} tracked fields{restrack_note}",
          flush=True)


if __name__ == "__main__":
    main()
