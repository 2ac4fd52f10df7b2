"""What the readers of the ``sdar`` family's per-layer metrics share
(``layer_metrics/block_attn_*.train.py``, ``mfu_blockdiff.train.py``). Every
function returns ``None`` where the run has nothing to read (another
family's cell, a program without the kernel or the counter, a rehearsal on a
CPU), and the metric is then left out of the line. ``keye_reads``' readers of
the program's counters and scopes serve this family as they are.
"""

from __future__ import annotations

from typing import Optional

from chipbench import counts, counts_sdar, keye_reads, trace_reads


def of_family(run) -> bool:
    return "block_length" in run.cfg and run.device.get("platform") == "tpu"


def kernel_roofline(run, kernel: str) -> Optional[float]:
    """The least time the chip could take for the kernel's model work of a
    step (operations over the bf16 peak or bytes over the bandwidth,
    whichever is larger) over the time its operations took, in %."""
    if not of_family(run):
        return None
    ms = trace_reads.kernel_ms_per_step(run, kernel)
    if not ms:
        return None
    c = run.counters
    work = counts_sdar.kernel_work(
        run.cfg, c["seq_len"], c["tokens_per_step"] // c["seq_len"])[kernel]
    peaks = counts.peaks_for(run.device["kind"])
    least = max(work["flops"] / peaks["bf16_flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)


def mfu(run) -> Optional[float]:
    """The traced calls' tokens per second times the model FLOPs a token
    (``counts_sdar.train_flops_per_token``, the experts by the program's own
    count of the pairs that landed here in those same calls) over the chip's
    bf16 peak times the chips used, in %."""
    if not of_family(run):
        return None
    pairs = keye_reads.pairs_here_per_step(run)
    rate = keye_reads.counted_rate(run)
    if pairs is None or rate is None:
        return None
    c = run.counters
    per_token = counts_sdar.train_flops_per_token(
        run.cfg, c["seq_len"], pairs / c["tokens_per_step"])
    peak = counts.peaks_for(run.device["kind"])["bf16_flops_per_s"]
    return 100.0 * rate * per_token / (peak * int(run.cell["chips"]))
