"""Model FLOP/s utilization of a looped training step, the share of the whole
step: the traced calls' tokens per second times the model FLOPs a token (the
blocks times the passes, the head after every pass, causal attention:
``chipbench/counts_ouro.py``) over the chip's bf16 peak times the chips used,
in %. Source: host_clock."""

from chipbench import ouro_reads


def read(run):
    return ouro_reads.mfu(run)
