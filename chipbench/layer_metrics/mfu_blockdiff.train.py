"""Model FLOP/s utilization of a block-diffusion training step's work done
here: the traced calls' tokens per second (a row is ``L`` tokens) times the
model FLOPs a token (both copies' projections and router, the held experts by
the program's own count, attention over the visible pairs, the head on the
noised copy: ``chipbench/counts_sdar.py``) over the chip's bf16 peak times
the chips used, in %. Source: host_clock."""

from chipbench import sdar_reads


def read(run):
    return sdar_reads.mfu(run)
