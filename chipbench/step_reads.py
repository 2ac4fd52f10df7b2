"""What the readers of a train step's parts share
(``layer_metrics/step_device_ms.train.py``, ``unnamed_ms_per_step.train.py``,
``fwd_``, ``bwd_``, ``remake_``, ``opt_ms_per_step.train.py`` and the parts'
own: ``attn_proj_``, ``embed_``, ``lm_head_``, ``router_``,
``mlp_ms_per_step.train.py``).

The program declares the parts of a step once
(``sparkflow_tpu/utils/tracing.py``: ``STEP_PARTS``, no part inside another)
and opens its ``jax.named_scope``s with those names; in a traced run of a mix
that sets ``trace_scopes`` the driver keeps the device seconds of the traced
stretch under every name on the operations' paths
(``drivers/train_fit_mesh.scope_seconds``), the program's scopes and JAX's own
path components alike (``jit``, ``jvp``, ``transpose``,
``rematted_computation``); a reader of one name asks
``keye_reads.scope_ms_per_step`` for it, as the older scope readers do. Every
function returns ``None`` where the run has
nothing to read (a mix without ``trace_scopes``, a program without the name
or, as before PR 40, without the list, a rehearsal on a CPU), and the metric
is then left out of the line.
"""

from __future__ import annotations

from typing import Optional, Tuple

from chipbench.keye_reads import scope_ms_per_step

ROOT = "jit"      # every operation that has a path has it under ``jit(run)``


def step_parts() -> Optional[Tuple[str, ...]]:
    """The program's list of a step's parts; ``None`` from a program that
    has none."""
    try:
        from sparkflow_tpu.utils.tracing import STEP_PARTS
    except ImportError:
        return None
    return STEP_PARTS


def step_device_ms(run) -> Optional[float]:
    """Device time of all the busiest program's operations of the traced
    stretch, per step."""
    return scope_ms_per_step(run, ROOT)


def unnamed_ms_per_step(run) -> Optional[float]:
    """A step's device time less the sum over the program's parts: what lies
    under no part's scope."""
    parts, whole = step_parts(), step_device_ms(run)
    if parts is None or whole is None:
        return None
    return whole - sum(scope_ms_per_step(run, p) or 0.0 for p in parts)


def fwd_ms_per_step(run) -> Optional[float]:
    """The forward pass: under JAX's ``jvp`` and not under its
    ``transpose``, which wraps a ``jvp`` wherever it is."""
    jvp = scope_ms_per_step(run, "jvp")
    if jvp is None:
        return None
    return jvp - (scope_ms_per_step(run, "transpose") or 0.0)
