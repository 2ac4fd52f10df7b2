"""What the readers of the ``keye_vl2`` family's per-layer metrics share
(``layer_metrics/*_roofline.train.py``, ``indexer_ms_per_step.train.py``,
``experts_ms_per_step.train.py``, ``mfu_active.train.py``, the two program
counters). Every function returns ``None`` where the run has nothing to read
(another family's cell, a program without the kernel, scope or counter, a
rehearsal on a CPU), and the metric is then left out of the line.
"""

from __future__ import annotations

from typing import Optional

from chipbench import counts, counts_keye_vl2, trace_reads


def steps_traced(run) -> int:
    c = run.counters
    calls = len(trace_reads.fit_calls(run))
    return calls * (c["tokens"] // c["calls"] // c["tokens_per_step"])


def counted_rate(run) -> Optional[float]:
    """Tokens per second of the calls the program's counters are of
    (``run.counters["counted_calls"]``: the traced calls), by the host's
    clock around each call."""
    c = run.counters
    calls = c.get("counted_calls")
    if not calls:
        return None
    seconds = sum(c["call_seconds"][i] for i in calls)
    return len(calls) * (c["tokens"] // c["calls"]) / seconds


def pairs_here_per_step(run) -> Optional[float]:
    """(token, expert) pairs a step routed to the experts held here, summed
    over the layers' mean (the program's counter ``expert_load`` over the
    steps of the traced calls, the stretch the kernels' times are of)."""
    load = (run.counters.get("model_metrics") or {}).get("expert_load")
    if load is None:
        return None
    return float(sum(sum(layer) for layer in load)) / len(load)


def scope_ms_per_step(run, scope: str) -> Optional[float]:
    """Device time of the operations traced under ``scope``, per optimizer
    step, mean over the devices, in ms (``train_fit_mesh`` keeps the scopes'
    seconds of the traced stretch)."""
    kept = run.counters.get("scope_seconds")
    steps = steps_traced(run) if kept else 0
    if not steps or scope not in kept["seconds"]:
        return None
    return 1e3 * kept["seconds"][scope] / (steps * kept["devices"])


def kernel_roofline(run, kernel: str) -> Optional[float]:
    """The least time the chip could take for the kernel's model work of a
    step (operations over the bf16 peak or bytes over the bandwidth,
    whichever is larger) over the time its operations took, in %."""
    if "sa_config" not in run.cfg or run.device.get("platform") != "tpu":
        return None
    ms = trace_reads.kernel_ms_per_step(run, kernel)
    pairs = pairs_here_per_step(run)
    if not ms or pairs is None:
        return None
    c = run.counters
    work = counts_keye_vl2.kernel_work(
        run.cfg, c["seq_len"], c["tokens_per_step"] // c["seq_len"],
        pairs)[kernel]
    peaks = counts.peaks_for(run.device["kind"])
    least = max(work["flops"] / peaks["bf16_flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
