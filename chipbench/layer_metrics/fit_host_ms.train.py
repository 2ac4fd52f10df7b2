"""Host time of one fit call: the wall time of its ``chipbench/fit_call``
span less the time the device was busy inside it, mean over the traced calls,
in ms. Source: device_trace."""

from chipbench import trace_reduce


def read(run):
    if run.reduced is None:
        return None
    spans = trace_reduce.host_spans(run.trace_data, run.counters["fit_span"])
    if not spans:
        return None
    host = [(e - s) - trace_reduce.busy_inside(run.reduced, (s, e))
            for s, e in spans]
    return 1e3 * sum(host) / len(host)
