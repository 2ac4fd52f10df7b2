"""``step_device_ms.train`` less the sum over the program's parts of a step
(``STEP_PARTS`` in ``sparkflow_tpu/utils/tracing.py``, which this reader
imports from the program), per optimizer step, in ms: the operations under no
part's scope (JAX's sums of the gradients over rows and passes, the loops'
slices and copies, and any scope forgotten). ``None`` for a program without
the list and in a run that keeps no scopes. Source: device_trace."""

from chipbench import step_reads


def read(run):
    return step_reads.unnamed_ms_per_step(run)
