"""Device time of the operations traced under the part ``router`` (an MoE
block's router, top-k and balance loss, and since PR 40 the block's second
norm, the experts' residual add and the layers' counters), per optimizer step,
in ms. ``None`` for a program without the scope. Source: device_trace."""

from chipbench import keye_reads


def read(run):
    return keye_reads.scope_ms_per_step(run, "router")
