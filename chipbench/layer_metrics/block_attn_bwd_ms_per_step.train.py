"""Device time of the block-mask attention's two backward kernels
(instruction names that contain ``block_attn_bwd_``: ``block_attn_bwd_dq``
and ``block_attn_bwd_dkv`` of ``ops/block_attention.py``) inside the traced
stretch, per optimizer step, in ms. Source: device_trace."""

from chipbench import trace_reads


def read(run):
    return trace_reads.kernel_ms_per_step(run, "block_attn_bwd_")
