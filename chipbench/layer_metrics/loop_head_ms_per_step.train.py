"""Device time of the operations traced under the scope ``loop_head``
(``models/looped_lm.py``: after every pass the final norm, the head's
logits, the cross-entropies and the exit gate; the exit distribution and its
entropy), forward and backward, all passes of a step, per optimizer step, in
ms. Source: device_trace."""

from chipbench import ouro_reads


def read(run):
    return ouro_reads.scope_ms_per_step(run, "loop_head")
