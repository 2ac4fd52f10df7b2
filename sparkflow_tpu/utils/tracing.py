"""Profiling/tracing: JAX profiler capture + named step annotations.

The reference has no tracing at all (SURVEY.md §5 — its only temporal control
is a fixed 8-second startup sleep). Here: ``trace(dir)`` captures a Perfetto/
TensorBoard-loadable profile of the wrapped region on TPU, and
``annotate(name)`` marks named ranges (visible in the trace viewer and nestable
inside jit via jax.named_scope).
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional

import jax


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False) -> Iterator[None]:
    """Capture a device+host profile of the enclosed region into ``log_dir``
    (open with TensorBoard's profile plugin or ui.perfetto.dev).

    The capture holds the device's operations, the runtime's host spans and
    this package's annotations (``span(..., jax_annotation=True)``: the
    ``train/...`` phases of a fit, the ``serving/...`` ticks of the decode
    plane). It leaves out what JAX adds by default, the Python tracer (every
    Python call as a host event) and the dump of each program's HLO: beside
    the 10^5 to 10^6 device events of a few training calls at real widths
    they made stopping and reading a capture take a quarter of an hour. For
    a program small enough to want them, ``jax.profiler.trace(log_dir)``
    itself keeps JAX's defaults."""
    os.makedirs(log_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, create_perfetto_link=create_perfetto_link,
                             profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named range: shows up in profiles; usable inside and outside jit."""
    with jax.named_scope(name):
        with jax.profiler.TraceAnnotation(name):
            yield


def device_memory_stats() -> dict:
    """Per-device live memory, when the backend exposes it."""
    out = {}
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            out[str(d)] = {k: stats[k] for k in
                           ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                           if k in stats}
    return out
