"""Device time of both flash-attention backward kernels (instruction names
that contain ``flash_bwd_``: ``flash_bwd_dq`` and ``flash_bwd_dkv`` of
``ops/attention.py``) inside the traced stretch, per optimizer step, in ms.
Source: device_trace."""

from chipbench import trace_reads


def read(run):
    return trace_reads.kernel_ms_per_step(run, "flash_bwd_")
