"""Device time of the flash-attention forward kernel (instruction names that
contain ``flash_fwd``: ``ops/attention.py``) inside the traced stretch, per
optimizer step, in ms. Source: device_trace."""

from chipbench import trace_reads


def read(run):
    return trace_reads.kernel_ms_per_step(run, "flash_fwd")
