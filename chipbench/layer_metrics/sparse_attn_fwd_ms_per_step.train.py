"""Device time of the selected-key attention's forward kernel (instruction
names that contain ``sparse_attn_fwd``: ``ops/sparse_attention.py``; with
rematerialised blocks it runs twice a step) inside the traced stretch, per
optimizer step, in ms. Source: device_trace."""

from chipbench import trace_reads


def read(run):
    return trace_reads.kernel_ms_per_step(run, "sparse_attn_fwd")
