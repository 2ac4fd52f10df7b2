"""Wall time of a fit's ``train/finish`` span (``Trainer.fit``: the losses'
mean and readback, the result), mean over the traced calls, in ms. Nothing is
queued behind it, so the device is idle for all of it. Source: device_trace
(the profiler's host plane)."""

from chipbench import trace_reads


def read(run):
    return trace_reads.span_mean_ms(run, "train/finish")
