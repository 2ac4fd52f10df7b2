"""Device time of the operations traced under the scope ``indexer``
(``models/sparse_moe_lm.py``: the index scores and the top-k selection, the
kernel ``sparse_attn_probs`` that makes the loss's target, the indexer's loss
and its backward pass), per optimizer step, in ms. Source: device_trace."""

from chipbench import keye_reads


def read(run):
    return keye_reads.scope_ms_per_step(run, "indexer")
