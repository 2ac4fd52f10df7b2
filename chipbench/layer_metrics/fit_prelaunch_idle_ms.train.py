"""How long the chip waited for a fit's work: device-idle time from the start
of a ``train/fit`` span to the start of its ``train/wait`` span (the interval
less the device-busy time inside it), mean over the traced calls, in ms.
Source: device_trace."""

from chipbench import trace_reduce


def read(run):
    if run.reduced is None:
        return None
    waits = trace_reduce.host_spans(run.trace_data, "train/wait")
    idle = []
    for s, e in trace_reduce.host_spans(run.trace_data, "train/fit"):
        inside = [w for w, _ in waits if s <= w <= e]
        if inside:
            upto = (s, inside[0])
            idle.append(upto[1] - upto[0]
                        - trace_reduce.busy_inside(run.reduced, upto))
    return 1e3 * sum(idle) / len(idle) if idle else None
