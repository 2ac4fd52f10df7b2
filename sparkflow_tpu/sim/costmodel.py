"""Replica cost models for the fleet simulator.

The simulator never runs a model — it *prices* each request against a
:class:`CostModel` whose coefficients are timings of a CPU rig, taken in
earlier rounds at toy widths. They are not a model of the chip: nothing
here has been fitted to a TPU, and a simulated latency is not a prediction
of a served one. Keeping the model
explicitly tiny (a handful of linear coefficients) is deliberate: the
point of the simulator is routing/policy dynamics at fleet scale, and for
those what matters is the *relative* cost structure (prefill scales with
prompt length, decode scales with output length and slows under
concurrency, KV pages scale with total tokens), not cycle accuracy.
:mod:`sparkflow_tpu.sim.calibrate` closes the loop by replaying the same
trace against a real fleet and pinning sim-vs-real agreement.

Default coefficients (``CostModel.from_bench_notes()``), kept because the
tests price fleets with them and only their ratios matter there:

- ``2.58`` ms a decoded token at low concurrency.
- ``10.9`` ms to a cold first token at ``prompt_len = 104``: prefill
  throughput ~= 104 / (10.9 - overhead) tokens/ms.
- the gap between tokens rising from 2.58 to ``6.92`` ms when a prefill
  and a full decode batch share the device: the ``decode_slowdown``
  contention coefficient.
- an int8 pool holding ``3.76x`` the pages per byte of an f32 pool (a
  byte count, not a timing): why heterogeneous ``kv_bytes_per_page``
  fleets exist at all (see the byte-headroom pick rule in
  ``serving/policies.py``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import List, Optional, Sequence

__all__ = ["CostModel"]


@dataclass(frozen=True)
class CostModel:
    """Prices one replica's work in simulated seconds.

    Parameters
    ----------
    ttft_base_ms : float
        Fixed per-request overhead before the first token (dispatch,
        dequeue, kernel launch).
    prefill_tokens_per_s : float
        Prompt tokens prefilled per second.
    decode_token_ms : float
        Per-output-token decode step time with an otherwise idle batch.
    decode_slowdown : float
        Linear contention coefficient: with ``active`` of ``slots``
        decode lanes busy, the per-token time scales by
        ``1 + decode_slowdown * active / slots``. From the CPU rig's
        gap between tokens with and without a prefill in the batch
        (6.92 / 2.58 at a full batch => slowdown ~= 1.7).
    predict_ms : float
        Flat service time for the predict (non-autoregressive) plane;
        the same contention factor applies.
    page_size : int
        KV page granularity in tokens (matches ``PagedKVCache``).
    net_rtt_ms : float
        Router<->replica round trip added to every request's latency.
    """

    ttft_base_ms: float = 2.0
    prefill_tokens_per_s: float = 9500.0
    decode_token_ms: float = 2.58
    decode_slowdown: float = 1.7
    predict_ms: float = 12.0
    page_size: int = 16
    net_rtt_ms: float = 0.5

    @staticmethod
    def from_bench_notes() -> "CostModel":
        """The CPU rig's defaults (see module docstring)."""
        return CostModel()

    def scaled(self, factor: float) -> "CostModel":
        """A copy with every *time* coefficient scaled by ``factor``
        (used by calibration to fit an unknown rig speed)."""
        return replace(
            self, ttft_base_ms=self.ttft_base_ms * factor,
            prefill_tokens_per_s=self.prefill_tokens_per_s / factor,
            decode_token_ms=self.decode_token_ms * factor,
            predict_ms=self.predict_ms * factor,
            net_rtt_ms=self.net_rtt_ms * factor)

    # -- pricing -----------------------------------------------------------

    def contention(self, active: int, slots: int) -> float:
        """Service-time multiplier with ``active`` of ``slots`` busy."""
        if slots <= 0:
            return 1.0
        frac = min(1.0, max(0, active) / float(slots))
        return 1.0 + self.decode_slowdown * frac

    def ttft_s(self, prompt_tokens: int, active: int, slots: int) -> float:
        """Dispatch-to-first-token time for a generate request."""
        prefill = prompt_tokens / self.prefill_tokens_per_s
        mult = self.contention(active, slots)
        return (self.ttft_base_ms + self.net_rtt_ms) / 1e3 + prefill * mult

    def decode_s(self, output_tokens: int, active: int,
                 slots: int) -> float:
        """First-token-to-done time for ``output_tokens`` tokens."""
        mult = self.contention(active, slots)
        return output_tokens * self.decode_token_ms * mult / 1e3

    def predict_s(self, active: int, slots: int) -> float:
        """Full service time for one predict request."""
        mult = self.contention(active, slots)
        return (self.predict_ms * mult + self.net_rtt_ms) / 1e3

    def pages_for(self, prompt_tokens: int, output_tokens: int) -> int:
        """KV pages a generate request pins for its lifetime."""
        total = max(1, prompt_tokens + output_tokens)
        return (total + self.page_size - 1) // self.page_size

    # -- fitting -----------------------------------------------------------

    @staticmethod
    def fit_predict(latencies_ms: Sequence[float],
                    base: Optional["CostModel"] = None) -> "CostModel":
        """Fit ``predict_ms`` from measured per-request latencies (median;
        robust to the tail the sim is supposed to *reproduce*, not
        consume as input)."""
        base = base or CostModel.from_bench_notes()
        if not latencies_ms:
            return base
        srt = sorted(float(x) for x in latencies_ms)
        med = srt[len(srt) // 2]
        # strip the modeled network RTT so it is not double counted
        return replace(base, predict_ms=max(0.1, med - base.net_rtt_ms))
