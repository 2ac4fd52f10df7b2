"""Device time of the operations traced under the part ``mlp`` (a dense
block's MLP half: norms, the projections, the residual add), forward, the
checkpoints' forward again and backward, per optimizer step, in ms. ``None``
for a program without the scope. Source: device_trace."""

from chipbench import keye_reads


def read(run):
    return keye_reads.scope_ms_per_step(run, "mlp")
