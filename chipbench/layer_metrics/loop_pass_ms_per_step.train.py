"""Device time of the operations traced under the scope ``loop_pass``
(``models/looped_lm.py``: the stack's blocks, the flash kernels among them),
forward, the checkpoints' forward again and backward, all passes of a step,
per optimizer step, in ms. Source: device_trace."""

from chipbench import ouro_reads


def read(run):
    return ouro_reads.scope_ms_per_step(run, "loop_pass")
