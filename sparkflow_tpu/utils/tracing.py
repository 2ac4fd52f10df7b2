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

# The parts of a train step: the ``jax.named_scope``s under which a profile
# splits a step's device time (``docs/observability.md``, item 3). Every
# operation that ``core._step_body`` and a model of the four decoder families
# (``models/transformer.py``, ``sparse_moe_lm.py``, ``block_diffusion_lm.py``,
# ``looped_lm.py``) write lies under exactly one part, and no part lies inside
# another, so the parts' times add up to the step's but for what JAX and XLA
# make themselves (the gradients' sums over a step's rows and passes, the
# loops' slices and copies). The program spells the names as literals and
# imports nothing from here; ``tests/test_step_parts.py`` holds both rules,
# and that no scope of a train step has a name this file does not know.
# JAX wraps a name in its transforms, so one scope gives the forward pass
# (``jvp(mlp)``), the backward pass (``transpose(jvp(mlp))``) and the
# checkpoints' forward again (the same under ``rematted_computation``).
STEP_PARTS = (
    "embed",             # ids to embedding rows (and learned positions), the cast
    "attn_proj",         # the attention half but its kernel: the norm(s), q/k/v,
                         # the q/k norms, rotary, the heads' layout, W_o, the
                         # residual add; the same name in all four families
    "flash_attention",   # dense blocks: the attention kernel's call
    "sparse_attention",  # sparse_moe_lm: attention over the selected keys
    "block_attention",   # block_diffusion_lm: attention under the block mask
    "indexer",           # sparse_moe_lm: index scores, top-k, the KL loss, its target
    "router",            # MoE blocks: the second norm, the router, top-k, the
                         # balance loss, the layers' counters, the residual add
    "experts",           # MoE blocks: the dropless experts (holds ``expert_rows``)
    "mlp",               # dense blocks: norm(s), the MLP, the residual add
    "lm_head",           # the final norm, the head's logits, the cross-entropy
                         # and the row's loss from it
    "loop_head",         # looped_lm, after every pass: the final norm, the head,
                         # the cross-entropies, the exit gate (``exit_gate``);
                         # after the last the exit distribution's entropy
    "batch",             # around the model: the ids' cast from the feed, the
                         # mean of the rows' losses, the counters' sums over rows
    "optimizer",         # the optimizer's update and its application
)
# Names that only group parts: the loss's ``value_and_grad``, a dense block's
# attention half (``attn_proj`` and ``flash_attention``), one pass of
# looped_lm's stack.
STEP_GROUPS = ("loss", "attention", "loop_pass")
# Names inside one part (``{name: part}``): their time is a share of the
# part's, not a term of the step's sum.
STEP_SUBPARTS = {"expert_rows": "experts", "exit_gate": "loop_head"}


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
