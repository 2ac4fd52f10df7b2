"""Fleet-simulator smoke: a 1000-replica x 1M-request what-if.

Run via ``make sim-smoke`` (or directly). The script

1. replays a 1,000,000-request synthetic trace (bursty MMPP arrivals,
   heavy-tail Pareto lengths, multi-turn sessions) against a simulated
   1000-replica heterogeneous fleet — 70% bf16 pools, 30% int8 pools
   with ~3.76x the pages per byte (an int8 page's bytes against an f32
   pool's) — using the REAL serving policies (``serving/policies.py``),
   real circuit breakers, and a CPU rig's cost model;
2. verifies the run is fully accounted (every request completed or
   rejected), byte-deterministic (stable event-log sha256), and bounded
   in wall-clock;
3. sweeps arrival rate on a smaller trace to produce a **capacity
   report**: the knee where tail latency and shedding take off — the
   what-if question ("can this fleet take 1.5x traffic?") the simulator
   exists to answer without touching production.

Everything is pure CPU; no servers, no sockets, no model. Exits nonzero
if accounting, determinism, or the wall-clock bound break.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sparkflow_tpu.sim import (CostModel, FleetSimulator, ReplicaSpec,
                               synthetic_trace)

SMOKE = bool(os.environ.get("SPARKFLOW_TPU_SMOKE"))
WALL_BOUND_S = 240.0          # generous CI bound; typical is well under
FLEET = 100 if SMOKE else 1000
REQUESTS = 50_000 if SMOKE else 1_000_000


def build_fleet(n):
    # 70/30 bf16/int8: same device bytes, int8 holds ~3.76x the pages
    # (its bytes a page against an f32 pool's), so byte-headroom routing
    # has real heterogeneity to work with
    specs = []
    for i in range(n):
        if i % 10 < 7:
            specs.append(ReplicaSpec(slots=8, pages_total=4096,
                                     kv_bytes_per_page=4 << 20))
        else:
            specs.append(ReplicaSpec(slots=8, pages_total=15400,
                                     kv_bytes_per_page=(4 << 20) * 4096
                                     // 15400))
    return specs


def main():
    cost = CostModel.from_bench_notes()
    specs = build_fleet(FLEET)

    print(f"== scale: {FLEET} replicas x {REQUESTS:,} requests ==")
    tr = synthetic_trace(REQUESTS, seed=7, rate_rps=40.0 * FLEET,
                         prompt_range=(16, 1024), output_range=(8, 256))
    rep = FleetSimulator(specs, tr, cost, mode="generate", seed=0).run()
    done = rep.completed + rep.rejected
    print(f"completed={rep.completed:,} rejected={rep.rejected:,} "
          f"queue_full={rep.queue_full:,} "
          f"p50={rep.latency_p50_ms:.1f}ms p95={rep.latency_p95_ms:.1f}ms")
    print(f"sim_time={rep.sim_time_s:.1f}s wall={rep.wall_s:.1f}s "
          f"({rep.completed / max(rep.wall_s, 1e-9):,.0f} sim-requests/s) "
          f"digest={rep.digest[:16]}")
    utils = sorted(r["utilization"] for r in rep.per_replica)
    print(f"replica utilization: min={utils[0]:.3f} "
          f"median={utils[len(utils) // 2]:.3f} max={utils[-1]:.3f}")
    ok = True
    if done != REQUESTS:
        print(f"FAIL: {REQUESTS - done} requests unaccounted")
        ok = False
    if rep.wall_s > WALL_BOUND_S:
        print(f"FAIL: wall {rep.wall_s:.1f}s > bound {WALL_BOUND_S}s")
        ok = False

    print(f"\n== capacity sweep: where does this fleet fall over? ==")
    # sessions off so the rate label IS the offered rate (session
    # follow-up turns trickle in over think-time tails and would dilute
    # the time-average far below the label)
    knee, base_p95 = None, None
    sweep_n = 12_000 if SMOKE else 120_000
    for rate in (30.0 * FLEET, 60.0 * FLEET, 90.0 * FLEET, 120.0 * FLEET):
        tr = synthetic_trace(sweep_n, seed=11, rate_rps=rate,
                             session_fraction=0.0,
                             prompt_range=(16, 1024),
                             output_range=(8, 256))
        r = FleetSimulator(specs, tr, cost, mode="generate", seed=0).run()
        shed = (r.rejected + r.queue_full) / sweep_n
        print(f"rate={rate:>8,.0f} rps  p95={r.latency_p95_ms:>9.1f}ms  "
              f"shed={shed:6.2%}  throughput={r.throughput_rps:,.0f} rps")
        if base_p95 is None:
            base_p95 = r.latency_p95_ms
        if knee is None and (shed > 0.01
                             or r.latency_p95_ms > 3.0 * base_p95):
            knee = rate
    if knee is not None:
        print(f"capacity knee: ~{knee:,.0f} rps on this fleet "
              f"(first rate with >1% shed or p95 > 3x the low-load p95)")
    else:
        print("capacity knee: beyond the swept range")

    print("\nsim-smoke", "OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
