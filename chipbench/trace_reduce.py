"""From a profiler trace (``.xplane.pb``) to device busy time, per-op time
and idle gaps named by what the host was doing.

What a TPU v5e trace holds (looked at by hand, PR 24): a plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
program execution), ``XLA Ops`` (one event per HLO instruction, its name the
instruction's text ``%name = shape opcode(...)``; a ``while`` is an event
that *contains* its body's events) and ``Async XLA Ops``; and a plane
``/host:CPU`` with one line per host thread (``python`` holds the
``TraceAnnotation`` spans and ``PjitFunction(...)``, the others the
runtime's own spans). Times are nanoseconds from the trace's start; the
device's clock lags the host's by about a millisecond (``clock_offset``).

Busy time is the union of the *leaf* events of ``XLA Ops``: an event that
contains another is a container (``while``, ``conditional``, ``call``) and
would otherwise cover the host's share of a loop. Read with nothing but JAX
(``jax.profiler.ProfileData``).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
_OP_NAME = re.compile(r"^%?([^\s=]+)")

Interval = Tuple[float, float]            # (start_s, end_s)
Event = Tuple[float, float, str]          # (start_s, end_s, name)


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` that ``jax.profiler.start_trace(trace_dir)``
    wrote."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _OP_NAME.match(text)
    return m.group(1) if m else text[:64]


def leaf_events(events: Sequence[Event]) -> List[Event]:
    """The events that contain no other event of the same line."""
    ordered = sorted(events, key=lambda e: (e[0], -e[1]))
    leaves: List[Event] = []
    stack: List[List] = []                # [event, has_child]
    for ev in ordered:
        while stack and stack[-1][0][1] <= ev[0]:
            done, has_child = stack.pop()
            if not has_child:
                leaves.append(done)
        if stack:
            stack[-1][1] = True
        stack.append([ev, False])
    for done, has_child in stack:
        if not has_child:
            leaves.append(done)
    return leaves


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _minus(whole: Interval, parts: Sequence[Interval]) -> List[Interval]:
    """``whole`` without the (sorted, disjoint) ``parts`` inside it."""
    out, at = [], whole[0]
    for s, e in parts:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if whole[1] > at:
        out.append((at, whole[1]))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of the sorted, disjoint ``intervals`` inside ``[lo, hi]``."""
    import bisect

    first = bisect.bisect_right(intervals, (lo,)) - 1
    out = []
    for s, e in intervals[max(first, 0):]:
        if s >= hi:
            break
        if min(e, hi) > max(s, lo):
            out.append((max(s, lo), min(e, hi)))
    return out


def read(path: str) -> Dict:
    """``{"devices": {n: {"ops": [Event], "modules": [Event]}},
    "host": {line_name: [Event]}}`` with times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict = {"devices": {}, "host": {}}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [(e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9, e.name)
                            for e in line.events]
            out["devices"][int(m.group(1))] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"][line.name] = [
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name) for e in line.events]
    return out


def host_spans(trace: Dict, name: str) -> List[Interval]:
    """Every host span called ``name`` (a ``TraceAnnotation``), in order."""
    return sorted((s, e) for evs in trace["host"].values()
                  for s, e, n in evs if n == name)


def _attribute_gaps(gaps: Sequence[Interval], host: Dict[str, List[Event]],
                    skip: Sequence[str]) -> Dict[str, float]:
    """Every instant of an idle gap goes to the innermost host span that
    covers it: the shortest one, over all threads. ``skip`` names spans that
    cover everything and say nothing (the profiler's own calls)."""
    import numpy as np

    spans = [ev for evs in host.values() for ev in evs
             if ev[1] > ev[0] and not any(ev[2].startswith(p) for p in skip)]
    starts = np.array([s for s, _, _ in spans], np.float64)
    ends = np.array([e for _, e, _ in spans], np.float64)
    by_name: Dict[str, float] = {}

    def add(name, seconds):
        by_name[name] = by_name.get(name, 0.0) + seconds

    for gs, ge in gaps:
        idx = (np.flatnonzero((ends > gs) & (starts < ge)) if spans
               else np.zeros(0, np.int64))
        if not len(idx):
            add("(no host span)", ge - gs)
            continue
        s_, e_ = np.clip(starts[idx], gs, ge), np.clip(ends[idx], gs, ge)
        points = np.unique(np.concatenate([[gs, ge], s_, e_]))
        mids = (points[:-1] + points[1:]) / 2
        covers = (s_[None, :] <= mids[:, None]) & (e_[None, :] > mids[:, None])
        length = np.where(covers, (ends[idx] - starts[idx])[None, :], np.inf)
        inner = np.argmin(length, axis=1)
        for k, piece in enumerate(np.diff(points)):
            add(spans[int(idx[inner[k]])][2] if covers[k].any()
                else "(no host span)", float(piece))
    return by_name


def clock_offset(trace: Dict) -> float:
    """Seconds to add to the device's times. The device's clock lags the
    host's by about a millisecond in a v5e trace, so a program seems to start
    before the host launched it; the least such lead over the trace's
    launches (``TpuLoadedExecutable::ExecuteLaunch``) is taken out. A device
    clock that runs ahead cannot be told from a slow launch and is left."""
    import bisect

    launches = sorted(s for evs in trace["host"].values() for s, _, n in evs
                      if n == "TpuLoadedExecutable::ExecuteLaunch")
    lead = 0.0
    for dev in trace["devices"].values():
        for start, _, _ in dev["modules"][:500]:
            i = bisect.bisect_right(launches, start + 3e-3) - 1
            if i >= 0 and start - launches[i] < -lead:
                lead = launches[i] - start
    return lead


_CLEAN = re.compile(r"[^A-Za-z0-9_.:/-]+")


def _clean(name: str) -> str:
    return _CLEAN.sub("_", name)[:80]


def _covered_before(intervals: Sequence[Interval]):
    """``f(t)``: how much of the sorted, disjoint ``intervals`` lies before
    each time of the array ``t``."""
    import numpy as np

    starts = np.array([s for s, _ in intervals], np.float64)
    ends = np.array([e for _, e in intervals], np.float64)
    total = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def f(t):
        t = np.asarray(t, np.float64)
        i = np.searchsorted(starts, t, side="right")   # intervals begun by t
        before = total[i]
        last = np.maximum(i - 1, 0)
        # the last begun interval may run past t: take the overhang off
        over = np.where(i > 0, np.maximum(ends[last] - t, 0.0), 0.0)
        return before - over

    return f


def reduce(trace: Dict, window: Optional[Interval] = None,
           min_gap_s: float = 5e-6, top: int = 10, most: int = 2000) -> Dict:
    """Busy and idle time of each device inside ``window`` (default: from the
    first to the last device op), the device operations that took most time,
    and the idle time by what it waited on: a gap inside a running program is
    the device's own (between two of its operations); a gap between programs
    goes to the host spans that cover it (the ``most`` longest are looked up).

    Returns ``{"window_s", "busy_s" (mean over devices), "idle_share",
    "per_device": {n: busy_s}, "device_ops": [[name, s]], "idle_gaps":
    [[name, s]], "busy": {n: [Interval]}}``.
    """
    import numpy as np

    if not trace["devices"]:
        raise ValueError("the trace holds no /device:TPU plane")
    shift = clock_offset(trace)
    leaves = {n: [(s + shift, e + shift, name)
                  for s, e, name in leaf_events(d["ops"])]
              for n, d in trace["devices"].items()}
    if window is None:
        every = [ev for evs in leaves.values() for ev in evs]
        if not every:
            raise ValueError("no operation ran on the device in the trace")
        window = (min(s for s, _, _ in every), max(e for _, e, _ in every))
    lo, hi = window
    busy, per_device = {}, {}
    ops: Dict[str, float] = {}
    short: Dict[str, str] = {}
    between: List[Interval] = []          # idle and outside every program
    by_device: Dict[str, float] = {}
    for n, evs in leaves.items():
        merged = clip(union([(s, e) for s, e, _ in evs]), lo, hi)
        busy[n] = merged
        per_device[n] = sum(e - s for s, e in merged)
        for s, e, name in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = short.get(name)
                if key is None:
                    key = short[name] = short_op_name(name)
                ops[key] = ops.get(key, 0.0) + d
        edges = np.array([lo] + [t for iv in merged for t in iv] + [hi])
        gs, ge = edges[0::2], edges[1::2]
        keep = ge > gs
        gs, ge = gs[keep], ge[keep]
        running = union([(s + shift, e + shift)
                         for s, e, _ in trace["devices"][n]["modules"]])
        covered = _covered_before(running) if running else None
        inside = (covered(ge) - covered(gs) if covered is not None
                  else np.zeros_like(gs))
        if inside.sum() > 0:
            by_device["(inside a running program)"] = by_device.get(
                "(inside a running program)", 0.0) + float(inside.sum())
        outside = (ge - gs) - inside
        longest = np.argsort(-outside)[:most]
        looked = longest[outside[longest] >= min_gap_s]
        for i in looked:
            gap = (float(gs[i]), float(ge[i]))
            between += _minus(gap, clip(running, *gap))
        rest = float(outside.sum() - outside[looked].sum())
        if rest > 0:
            by_device["(shorter gaps)"] = by_device.get(
                "(shorter gaps)", 0.0) + rest
    ndev = len(leaves)
    by_span = _attribute_gaps(between, trace["host"],
                              skip=("$profiler.py", "$threading.py"))
    for k, v in by_device.items():
        by_span[k] = by_span.get(k, 0.0) + v
    rank = lambda d, scale: [[_clean(k), v / scale] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    busy_s = sum(per_device.values()) / ndev
    return {"window_s": hi - lo, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / (hi - lo),
            "per_device": per_device, "device_ops": rank(ops, ndev),
            "idle_gaps": rank(by_span, ndev), "busy": busy}


def busy_inside(reduced: Dict, span: Interval) -> float:
    """Device-busy seconds inside ``span``, averaged over the devices."""
    total = sum(e - s for merged in reduced["busy"].values()
                for s, e in clip(merged, *span))
    return total / max(1, len(reduced["busy"]))
