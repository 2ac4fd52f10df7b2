"""Roofline share of the kernel ``expert_gmm`` (instruction names that contain
it): the least time the chip could take for its model work of a step
(``chipbench/counts_keye_vl2.py``, ``chipbench/peaks.json``) over the device
time its operations took, in %. Recomputed and masked-out work earns no
credit. Source: device_trace."""

from chipbench import keye_reads


def read(run):
    return keye_reads.kernel_roofline(run, "expert_gmm")
