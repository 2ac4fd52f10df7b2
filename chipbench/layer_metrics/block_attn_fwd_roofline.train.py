"""Roofline share of the kernel ``block_attn_fwd`` (instruction names that
contain it): the least time the chip could take for its model work of a step
(``chipbench/counts_sdar.py``: the pairs the rule leaves visible;
``chipbench/peaks.json``) over the device time its operations took, in %.
Masked pairs inside a visited tile and recomputed scores earn no credit.
Source: device_trace."""

from chipbench import sdar_reads


def read(run):
    return sdar_reads.kernel_roofline(run, "block_attn_fwd")
