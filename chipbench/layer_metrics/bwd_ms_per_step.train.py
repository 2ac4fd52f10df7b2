"""Device time of the backward pass, per optimizer step, in ms: the operations
whose path holds JAX's ``transpose``, the checkpoints' forward again among
them (``remake_ms_per_step.train``). ``None`` in a run that keeps no scopes.
Source: device_trace."""

from chipbench import keye_reads


def read(run):
    return keye_reads.scope_ms_per_step(run, "transpose")
