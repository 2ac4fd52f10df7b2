"""What the readers of the program's own spans and kernel names share
(``layer_metrics/fit_*_ms.train.py``, ``flash_*_ms_per_step.train.py``).

The program names its host phases (``train/fit`` and under it ``train/plan``,
``train/init_state``, ``train/transfer``, ``train/launch``, ``train/wait``,
``train/finish``: ``Trainer.fit``) and its kernels (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``: ``ops/attention.py``). The compiler wraps a
kernel's name in those of the transforms around it (``jvp_flash_fwd_.1``), so a
kernel is matched by *contains*, on the instruction's own name and not on its
text, which also names its operands. A program without the span or the kernel
(the parent of the PR that added them) gives ``None``, and the metric is left
out of the line.
"""

from __future__ import annotations

from typing import List, Optional

from chipbench import trace_reduce
from chipbench.trace_reduce import Interval


def fit_calls(run) -> List[Interval]:
    """The driver's span around each traced call, in order; none where
    the trace has no device plane (a rehearsal on a CPU)."""
    if run.reduced is None:
        return []
    return trace_reduce.host_spans(run.trace_data, run.counters["fit_span"])


def span_mean_ms(run, name: str) -> Optional[float]:
    """Mean wall time of the host spans called ``name`` in the trace, in ms."""
    if run.reduced is None:
        return None
    spans = trace_reduce.host_spans(run.trace_data, name)
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)


def kernel_ms_per_step(run, needle: str) -> Optional[float]:
    """Device time of the operations whose instruction name contains
    ``needle``, inside the traced stretch, over the optimizer steps in it,
    mean over the devices, in ms. A kernel's custom call holds no other
    operation, so the leaves among the matching events are the matching
    leaves of the whole line."""
    calls = fit_calls(run)
    devices = run.trace_data["devices"] if calls else {}
    if not devices:
        return None
    lo, hi = calls[0][0], calls[-1][1]
    shift = trace_reduce.clock_offset(run.trace_data)
    c = run.counters
    steps = len(calls) * (c["tokens"] // c["calls"] // c["tokens_per_step"])
    total, found = 0.0, False
    for dev in devices.values():
        # the text holds the name: a substring test first spares a million
        # events the regular expression
        hits = [ev for ev in dev["ops"] if needle in ev[2]
                and needle in trace_reduce.short_op_name(ev[2])]
        for s, e, _ in trace_reduce.leaf_events(hits):
            found = True
            total += max(0.0, min(e + shift, hi) - max(s + shift, lo))
    if not found or steps <= 0:
        return None
    return 1e3 * total / (steps * len(devices))
