"""The device's idle share of the traced stretch: 1 - the union of the leaf
device-op intervals over the stretch, in %. Source: device_trace."""


def read(run):
    return None if run.reduced is None else 100.0 * run.reduced["idle_share"]
