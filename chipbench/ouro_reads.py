"""What the readers of the ``ouro`` family's per-layer metrics share
(``layer_metrics/mfu_loop.train.py``, ``loop_*_ms_per_step.train.py``,
``loop_exit_entropy.train.py``). Every function returns ``None`` where the
run has nothing to read (another family's cell, a program without the scope
or the counter, as the parent of PR 36, a rehearsal on a CPU), and the metric
is then left out of the line. ``keye_reads``' readers of the driver's kept
scopes and counted calls serve this family as they are.
"""

from __future__ import annotations

import math
from typing import Optional

from chipbench import counts, counts_ouro, keye_reads


def of_family(run) -> bool:
    return "total_ut_steps" in run.cfg


def mfu(run) -> Optional[float]:
    """The traced calls' tokens per second times the model FLOPs a token
    (``counts_ouro.train_flops_per_token``: the blocks every pass, the head
    every pass, causal attention) over the chip's bf16 peak times the chips
    used, in %: the share of the whole step."""
    if not of_family(run) or run.device.get("platform") != "tpu":
        return None
    rate = keye_reads.counted_rate(run)
    if rate is None:
        return None
    per_token = counts_ouro.train_flops_per_token(
        run.cfg, run.counters["seq_len"])
    peak = counts.peaks_for(run.device["kind"])["bf16_flops_per_s"]
    return 100.0 * rate * per_token / (peak * int(run.cell["chips"]))


def scope_ms_per_step(run, scope: str) -> Optional[float]:
    if not of_family(run):
        return None
    return keye_reads.scope_ms_per_step(run, scope)


def exit_entropy_share(run) -> Optional[float]:
    """The program's counter ``exit_entropy`` (the mean entropy of a
    position's exit distribution over the steps of the counted calls) over
    ``log(total_ut_steps)``, what a uniform exit distribution has, in %."""
    entropy = (run.counters.get("model_metrics") or {}).get("exit_entropy")
    if entropy is None or not of_family(run):
        return None
    return 100.0 * entropy / math.log(run.cfg["total_ut_steps"])
