"""Device time of the kernels that bring ``q`` and ``k`` from a projection's
output to the attention kernels' layout (instruction names that contain
``head_rotary_``: ``head_rotary_fwd`` and ``head_rotary_bwd`` of
``ops/head_rotary.py``: per-head RMSNorm, rotary positions, the head-major
layout, and their transpose) inside the traced stretch, per optimizer step,
in ms; a part of ``attn_proj_ms_per_step.train``. ``None`` for a program
without the kernels. Source: device_trace."""

from chipbench import trace_reads


def read(run):
    return trace_reads.kernel_ms_per_step(run, "head_rotary_")
