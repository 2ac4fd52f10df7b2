"""Device time of the operations traced under the part ``attn_proj`` (the
attention half of a block but its kernel: norms, q/k/v, the q/k norms, rotary,
the heads' layout, ``W_o``, the residual add; one name in all four families),
forward, the checkpoints' forward again and backward, per optimizer step, in
ms. ``None`` for a program without the scope. Source: device_trace."""

from chipbench import keye_reads


def read(run):
    return keye_reads.scope_ms_per_step(run, "attn_proj")
