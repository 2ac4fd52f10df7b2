"""Programs a fit call launches: ``XLA Modules`` events (on the host's clock,
as ``reduce`` puts them) that start inside a ``chipbench/fit_call`` span, mean
over the traced calls and the devices. One is the fused training program; the
rest are the fit's host work, a tiny program per copied or initialised leaf.
Source: device_trace."""

from chipbench import trace_reads, trace_reduce


def read(run):
    calls = trace_reads.fit_calls(run)
    devices = run.trace_data["devices"] if calls else {}
    if not devices:
        return None
    shift = trace_reduce.clock_offset(run.trace_data)
    started = sum(1 for dev in devices.values() for s, _, _ in dev["modules"]
                  if any(a <= s + shift < b for a, b in calls))
    return started / (len(calls) * len(devices))
