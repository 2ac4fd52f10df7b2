"""Model FLOP/s utilization of the traced run: its tokens per second times
the model FLOPs per token (``chipbench/counts.py``) over the chip's bf16 peak
(``chipbench/peaks.json``) times the chips used, in %. Source: host_clock."""

from chipbench import counts


def read(run):
    if run.device["platform"] != "tpu":
        return None                       # a CPU has no peak to take a share of
    peak = counts.peaks_for(run.device["kind"])["bf16_flops_per_s"]
    per_token = counts.train_flops_per_token(run.cfg, run.counters["seq_len"])
    rate = run.end_to_end["train_tokens_per_s"]
    return 100.0 * rate * per_token / (peak * int(run.cell["chips"]))
