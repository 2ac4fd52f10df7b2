"""Device time of one program's operations by the scope they were traced in:
``python3 -m chipbench.op_scopes <xplane.pb> [--module jit_run] [--depth 3]``.

The program wraps a train step's phases in ``jax.named_scope`` (``loss`` and
``optimizer`` in ``core._step_body``; ``embed``, ``attention``, ``mlp``,
``lm_head`` in ``models/transformer.py``), and JAX adds a ``transpose(...)``
component to the path of every operation of the backward pass. A device trace
keeps each operation's path (``jit(run)/.../loss/attention/dot_general:``) as
the stat ``tf_op`` of the event's *metadata*, which
``jax.profiler.ProfileData`` does not hand out. So this file reads the
``.xplane.pb`` itself, with a short reader of the protobuf wire format and
nothing else (``tensorflow``'s ``xplane_pb2`` is not promised on the chip's
machine): of an ``XSpace`` it follows ``planes`` -> ``lines`` ("XLA Ops",
"XLA Modules") -> ``events``, and ``event_metadata`` -> ``stats`` (``tf_op``,
``program_id``) named by ``stat_metadata``.

A tool for an operator's or a builder's capture (``utils.tracing.trace()``):
the benchmark's run deletes its trace once ``trace_reduce.read`` has kept each
event's time and text, so the per-layer metrics by phase wait for an edit
there (PERF.md section 7).
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

from chipbench import trace_reduce

PHASES = ("forward", "backward", "optimizer", "other")
BLOCKS = ("embed", "attention", "mlp", "lm_head")
_MODULE = re.compile(r"^(.*)\((\d+)\)$")

Op = Tuple[float, str, str]               # (seconds, instruction name, path)


# -- the wire format: (field number, wire type, value) of one message ---------


def _varint(buf, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """Every field of the message in ``buf``: a varint's value, the bytes
    of a length-delimited field (a ``memoryview``, uncopied), or the raw
    bytes of a fixed-width one."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"wire type {wire} is not in an xplane")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> Tuple[int, object]:
    """``map<int64, Message>`` is a repeated entry of key = 1, value = 2."""
    key, value = 0, b""
    for number, _, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


# -- XSpace -> what the ops of each device were, when, and under which path ---


def _stat(buf) -> Tuple[int, object]:
    """An ``XStat``: ``(metadata_id, value)``; strings as text, a reference
    (``ref_value``, field 7) as ``("ref", id)``."""
    ident, value = 0, None
    for number, wire, v in _fields(buf):
        if number == 1:
            ident = v
        elif number == 5:
            value = _text(v)
        elif number == 7:
            value = ("ref", v)
        elif wire == 0:
            value = v
    return ident, value


def _event_metadata(buf, stat_names: Dict[int, str]) -> Dict[str, object]:
    out: Dict[str, object] = {"name": ""}
    for number, _, v in _fields(buf):
        if number == 2:
            out["name"] = _text(v)
        elif number == 5:
            ident, value = _stat(v)
            if isinstance(value, tuple):            # a reference to a name
                value = stat_names.get(value[1], "")
            out[stat_names.get(ident, str(ident))] = value
    return out


def _line(buf) -> Tuple[str, List[Tuple[int, int, int]]]:
    """An ``XLine``: its name and ``(offset_ps, duration_ps, metadata_id)``
    of each event."""
    name, events = "", []
    for number, _, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 4:
            meta = offset = duration = 0
            for n, _, x in _fields(v):
                if n == 1:
                    meta = x
                elif n == 2:
                    offset = x
                elif n == 3:
                    duration = x
            events.append((offset, duration, meta))
    return name, events


def read(path: str) -> Dict[int, Dict]:
    """``{device: {"modules": {name: (program_id, seconds)}, "ops":
    [(program_id, seconds, instruction name, path)]}}``: the leaf events of
    each TPU plane's "XLA Ops" line (an event that contains another is a
    ``while`` or a ``call`` and would count its body twice), each with the
    path its metadata's ``tf_op`` gives."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[int, Dict] = {}
    for number, _, plane in _fields(space):
        if number != 1:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for n, _, v in _fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 3:
                lines.append(v)
            elif n == 4:
                metas.append(v)
            elif n == 5:
                ident, meta = _map_entry(v)
                stat_names[ident] = next(
                    (_text(x) for k, _, x in _fields(meta) if k == 2), "")
        m = trace_reduce.DEVICE_PLANE.match(name)
        if not m:
            continue
        metadata = {}
        for entry in metas:
            ident, meta = _map_entry(entry)
            metadata[ident] = _event_metadata(meta, stat_names)
        dev: Dict = {"modules": {}, "ops": []}
        for buf in lines:
            line_name, events = _line(buf)
            if line_name == trace_reduce.MODULES_LINE:
                for _, duration, meta in events:
                    mm = _MODULE.match(metadata[meta]["name"])
                    if mm:
                        ident, seconds = dev["modules"].get(
                            mm.group(1), (int(mm.group(2)), 0.0))
                        dev["modules"][mm.group(1)] = (
                            ident, seconds + duration * 1e-12)
            elif line_name == trace_reduce.OPS_LINE:
                leaves = trace_reduce.leaf_events(
                    [(o, o + d, meta) for o, d, meta in events])
                for start, end, meta in leaves:
                    md = metadata[meta]
                    dev["ops"].append((
                        md.get("program_id"), (end - start) * 1e-12,
                        trace_reduce.short_op_name(md["name"]),
                        str(md.get("tf_op") or "").rstrip(":")))
        out[int(m.group(1))] = dev
    return out


def program_ops(devices: Dict[int, Dict], module: Optional[str] = None
                ) -> Tuple[str, List[Op]]:
    """The ops of one program over all devices: ``module`` by name
    (``jit_run``), or the one the devices spent most time in."""
    seconds: Dict[str, float] = {}
    idents: Dict[str, int] = {}
    for dev in devices.values():
        for name, (ident, s) in dev["modules"].items():
            seconds[name] = seconds.get(name, 0.0) + s
            idents[name] = ident
    if module is None:
        if not seconds:
            raise ValueError("the trace holds no program on a TPU plane")
        module = max(seconds, key=seconds.get)
    if module not in idents:
        raise ValueError(f"no program {module!r} in the trace "
                         f"(has {sorted(idents)})")
    return module, [(s, name, path) for dev in devices.values()
                    for ident, s, name, path in dev["ops"]
                    if ident == idents[module]]


def _components(path: str) -> List[str]:
    """``a/transpose(jvp(b/c))/d`` -> ``[a, transpose(jvp(b/c)), d]``: a
    slash inside brackets belongs to its component."""
    parts, depth, at = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth = max(0, depth - 1)
        elif ch == "/" and depth == 0:
            parts.append(path[at:i])
            at = i + 1
    parts.append(path[at:])
    return parts


def by_scope(ops: List[Op], depth: int) -> Dict[str, float]:
    """Seconds by the first ``depth`` components of each op's path."""
    out: Dict[str, float] = {}
    for seconds, _, path in ops:
        key = "/".join(_components(path)[:depth]) if path else "(no path)"
        out[key] = out.get(key, 0.0) + seconds
    return out


def phase_of(path: str) -> Tuple[str, str]:
    """``(phase, block)`` of a path: under the ``optimizer`` scope, or under
    ``loss`` with (backward) or without (forward) a ``transpose(`` in it;
    the block is the model's scope it passes through, if any. JAX wraps a
    scope's name in its transforms (``transpose(jvp(attention))``), so names
    are looked for between slashes and brackets; the leading ``jit(<name>)``
    is the program's and no scope."""
    parts = _components(path)
    if parts and parts[0].startswith(("jit(", "pjit(")):
        parts = parts[1:]
    names = set(re.split(r"[/()]+", "/".join(parts)))
    if "optimizer" in names:
        phase = "optimizer"
    elif "loss" in names:
        phase = ("backward" if any("transpose(" in p for p in parts)
                 else "forward")
    else:
        phase = "other"
    return phase, next((b for b in BLOCKS if b in names), "")


def by_phase(ops: List[Op]) -> Dict[str, Dict[str, float]]:
    """``{phase: {"total": s, block: s, ...}}`` over ``PHASES``."""
    out: Dict[str, Dict[str, float]] = {p: {"total": 0.0} for p in PHASES}
    for seconds, _, path in ops:
        phase, block = phase_of(path)
        row = out[phase]
        row["total"] += seconds
        if block:
            row[block] = row.get(block, 0.0) + seconds
    return out


def top_ops(ops: List[Op], n: int) -> Dict[str, List[List]]:
    """The ``n`` instructions of each phase that took most time, as
    ``[name, seconds, path]``: what a phase, and above all ``other``, is
    made of. A fusion has one path, its root's: an optimizer update that
    the compiler fused into a gradient's fusion counts under ``backward``."""
    rows: Dict[str, Dict[str, List]] = {p: {} for p in PHASES}
    for seconds, name, path in ops:
        row = rows[phase_of(path)[0]].setdefault(name, [name, 0.0, path])
        row[1] += seconds
    return {p: sorted(r.values(), key=lambda x: -x[1])[:n]
            for p, r in rows.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--module", default=None,
                    help="the program's name in the trace (jit_run); "
                         "default: the one with most device time")
    ap.add_argument("--depth", type=int, default=3,
                    help="components of the path the scope table keeps")
    ap.add_argument("--top", type=int, default=20,
                    help="rows of the scope table; a quarter as many "
                         "instructions are listed for each phase")
    args = ap.parse_args(argv)
    module, ops = program_ops(read(args.xplane), args.module)
    total = sum(s for s, _, _ in ops)
    scopes = sorted(by_scope(ops, args.depth).items(), key=lambda kv: -kv[1])
    print(json.dumps({"module": module, "ops": len(ops), "device_s": total,
                      "by_phase": by_phase(ops),
                      "by_scope": scopes[:args.top],
                      "top_ops": top_ops(ops, max(1, args.top // 4))},
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
