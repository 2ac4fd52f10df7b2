"""Peak device memory after the window, the allocator's
``peak_bytes_in_use`` on the fullest chip, in GB. Source: program_counter."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
