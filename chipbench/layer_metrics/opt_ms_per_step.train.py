"""Device time of the operations traced under the scope ``optimizer``
(``core._step_body``: the optimizer's update and its application), per
optimizer step, in ms. ``None`` in a run that keeps no scopes. Source:
device_trace."""

from chipbench import keye_reads


def read(run):
    return keye_reads.scope_ms_per_step(run, "optimizer")
