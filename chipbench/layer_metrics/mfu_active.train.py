"""Model FLOP/s utilization of the work done here: the traced calls' tokens
per second times the model FLOPs a token of the selected keys, the held
experts (by the program's own count of the pairs that landed here in those
same calls) and the held vocabulary (``chipbench/counts_keye_vl2.py``), over the chip's bf16
peak times the chips used, in %. Source: host_clock."""

from chipbench import counts, counts_keye_vl2, keye_reads


def read(run):
    if "sa_config" not in run.cfg or run.device["platform"] != "tpu":
        return None
    pairs = keye_reads.pairs_here_per_step(run)
    rate = keye_reads.counted_rate(run)
    if pairs is None or rate is None:
        return None
    c = run.counters
    per_token = counts_keye_vl2.train_flops_per_token(
        run.cfg, c["seq_len"], pairs / c["tokens_per_step"])
    peak = counts.peaks_for(run.device["kind"])["bf16_flops_per_s"]
    return 100.0 * rate * per_token / (peak * int(run.cell["chips"]))
