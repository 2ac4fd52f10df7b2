"""Device time of the block-mask attention's forward kernel (instruction
names that contain ``block_attn_fwd``: ``ops/block_attention.py``) inside the
traced stretch, per optimizer step, in ms. Source: device_trace."""

from chipbench import trace_reads


def read(run):
    return trace_reads.kernel_ms_per_step(run, "block_attn_fwd")
