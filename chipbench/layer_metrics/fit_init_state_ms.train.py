"""Wall time of a fit's ``train/init_state`` span (``Trainer.fit``: the copy
of ``init_params``, placement, the optimizer's fresh state), mean over the
traced calls, in ms. A wall time inside the pre-launch interval: read beside
``fit_prelaunch_idle_ms.train``, not added to it. Source: device_trace (the
profiler's host plane)."""

from chipbench import trace_reads


def read(run):
    return trace_reads.span_mean_ms(run, "train/init_state")
