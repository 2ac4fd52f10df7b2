"""Device time of the operations of the busiest program (the fused fit's
``jit_run``) over the traced stretch, per optimizer step, in ms: the root of
every operation's path, what the parts of a step
(``sparkflow_tpu/utils/tracing.py``) and ``unnamed_ms_per_step.train`` add up
to. ``None`` in a run that keeps no scopes (a mix without ``trace_scopes``).
Source: device_trace."""

from chipbench import step_reads


def read(run):
    return step_reads.step_device_ms(run)
