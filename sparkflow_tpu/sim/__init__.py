"""Fleet-scale trace-driven simulation: what-if the fleet, not the model.

The north-star system serves heavy traffic from a large fleet — but every
routing/health/canary policy question ("what does the pick rule do to a
mixed int8/bf16 fleet at 4x burst?") is unanswerable on a 3-replica test
rig and unaffordable to answer in production. This package answers them
offline: a deterministic discrete-event simulator
(:class:`~sparkflow_tpu.sim.core.FleetSimulator`) replays a request trace
(:mod:`~sparkflow_tpu.sim.trace`) against a modelled fleet whose
*decisions* are made by the real serving plane's policy code
(:mod:`sparkflow_tpu.serving.policies`, plus the real ``CircuitBreaker``,
``TokenBucket``, ``CanaryController``, and ``RetryPolicy`` on a virtual
clock) while transport + compute are priced by a small linear
:class:`~sparkflow_tpu.sim.costmodel.CostModel`. Calibration
(:mod:`~sparkflow_tpu.sim.calibrate`) pins sim-vs-real agreement on the
same trace; determinism is byte-exact (same trace + seed => identical
event-log sha256).

See ``docs/sim.md``; ``make sim-smoke`` runs a 1000-replica x 1M-request
what-if end to end.
"""

from .core import (FleetSimulator, ReplicaSpec, SimAutoscaler, SimReplica,
                   SimReport, legacy_generate_pick_key)
from .costmodel import CostModel
from .trace import Request, load, save, synthetic_trace

# NOTE: `calibrate` is deliberately NOT imported here — it pulls the full
# serving stack (and through it JAX); `from sparkflow_tpu.sim import
# calibrate` loads it on demand. Pure-sim runs stay import-light.
__all__ = ["FleetSimulator", "ReplicaSpec", "SimAutoscaler", "SimReplica",
           "SimReport", "legacy_generate_pick_key", "CostModel", "Request",
           "synthetic_trace", "save", "load"]
