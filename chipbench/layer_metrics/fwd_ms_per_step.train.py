"""Device time of the forward pass, per optimizer step, in ms: the operations
whose path holds JAX's ``jvp`` and not its ``transpose`` (under ``loss`` every
operation of the model is one or the other). ``None`` in a run that keeps no
scopes. Source: device_trace."""

from chipbench import step_reads


def read(run):
    return step_reads.fwd_ms_per_step(run)
