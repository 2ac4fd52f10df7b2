"""Device time of what the blocks' and the head's checkpoints compute again in
the backward pass (JAX's path component ``rematted_computation``), per
optimizer step, in ms; a part of ``bwd_ms_per_step.train``. ``None`` in a run
that keeps no scopes. Source: device_trace."""

from chipbench import keye_reads


def read(run):
    return keye_reads.scope_ms_per_step(run, "rematted_computation")
