"""Device time of the operations traced under the scope ``experts``
(``models/sparse_moe_lm.py``: the rows' layout, dispatch and combine, the
kernels ``expert_gmm`` and ``expert_tgmm``), forward and backward, per
optimizer step, in ms. Source: device_trace."""

from chipbench import keye_reads


def read(run):
    return keye_reads.scope_ms_per_step(run, "experts")
