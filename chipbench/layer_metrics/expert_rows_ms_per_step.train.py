"""Device time of the operations traced under the scope ``expert_rows``
(``ops/grouped_matmul.py``: the tokens' movement into the experts' rows and
back, forward and backward: the kernels ``expert_rows_in`` and
``expert_rows_out`` and what prepares their operands), per optimizer step, in
ms; a part of ``experts_ms_per_step.train``. ``None`` for a program without
the scope. Source: device_trace."""

from chipbench import keye_reads


def read(run):
    return keye_reads.scope_ms_per_step(run, "expert_rows")
