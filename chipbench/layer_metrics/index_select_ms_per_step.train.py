"""Device time of the indexer's selection kernel (instruction names that
contain ``index_select``: ``ops/sparse_attention.py``; the index scores' tiles
and the two bisections of each block of queries; with rematerialised blocks it
runs twice a step in every layer and row) inside the traced stretch, per
optimizer step, in ms. Part of what ``indexer_ms_per_step.train`` lumps.
Source: device_trace."""

from chipbench import trace_reads


def read(run):
    return trace_reads.kernel_ms_per_step(run, "index_select")
