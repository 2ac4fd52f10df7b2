"""Device time of the operations traced under the part ``lm_head`` (the final
norm, the head's logits, and since PR 40 the cross-entropy and the row's loss
from it), forward, the stretches' forward again and backward, per optimizer
step, in ms. ``None`` for a program without the scope (``looped_lm``: its
head is ``loop_head``). Source: device_trace."""

from chipbench import keye_reads


def read(run):
    return keye_reads.scope_ms_per_step(run, "lm_head")
