"""Device time of the indexer's loss kernels (instruction names that contain
``index_kl``: ``index_kl_fwd``, ``index_kl_bwd_dq`` and ``index_kl_bwd_dk`` of
``ops/sparse_attention.py``; the forward runs twice a step in every layer and
row with rematerialised blocks) inside the traced stretch, per optimizer step,
in ms. Part of what ``indexer_ms_per_step.train`` lumps. Source:
device_trace."""

from chipbench import trace_reads


def read(run):
    return trace_reads.kernel_ms_per_step(run, "index_kl")
