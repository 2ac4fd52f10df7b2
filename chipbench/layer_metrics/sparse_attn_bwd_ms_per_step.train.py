"""Device time of the selected-key attention's two backward kernels
(instruction names that contain ``sparse_attn_bwd_``: ``sparse_attn_bwd_dq``
and ``sparse_attn_bwd_dkv`` of ``ops/sparse_attention.py``) inside the traced
stretch, per optimizer step, in ms. Source: device_trace."""

from chipbench import trace_reads


def read(run):
    return trace_reads.kernel_ms_per_step(run, "sparse_attn_bwd_")
