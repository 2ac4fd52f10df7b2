"""One run of one cell: ``python3 -m chipbench.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>``, from the root of a checkout.

Everything that belongs to one cell is found by the names in
``BENCHMARK.json``: the configuration's file, the traffic file, the driver the
traffic file names (``chipbench/drivers/<driver>.py``), and one reader per
per-layer metric (``chipbench/layer_metrics/<metric>.py``). This file knows no
cell, no model and no metric.

Lines printed before the last are JSON objects with a ``"line"`` key (the
set-up split, per-call observations, the numbers compared). The
last line of standard output is the result.
"""

from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

from chipbench import traffic as traffic_mod  # noqa: E402


def process_start_time() -> float:
    """When this process began, on ``time.time()``'s clock: ``setup_s`` runs
    from here, so the interpreter's own start-up counts."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        started = boot + ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= _T_IMPORT - started < 600:
            return started
    except (OSError, ValueError, StopIteration, IndexError):
        pass
    return _T_IMPORT


def load_by_path(path: str, name: str):
    """Import one file under ``chipbench/`` whose name need not be an
    identifier (``mfu.train.py``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


class Run:
    """What a driver and a reader are handed: the cell's files, the
    arguments, a log, and a clock for the set-up split."""

    def __init__(self, bench: dict, workload: str, seed: int, seconds: float,
                 trace: bool, rehearse: bool):
        self.bench = bench
        self.cell = next((w for w in bench["workloads"]
                          if w["name"] == workload), None)
        if self.cell is None:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                             f"(has {[w['name'] for w in bench['workloads']]})")
        entry = next(c for c in bench["configs"]
                     if c["name"] == self.cell["config"])
        with open(os.path.join(ROOT, entry["file"])) as f:
            self.cfg = json.load(f)
        self.cfg_dir = os.path.dirname(os.path.join(ROOT, entry["file"]))
        self.mix = traffic_mod.load(self.cell["traffic"])
        if rehearse:
            self.cfg = traffic_mod.merged(self.cfg, self.cfg.get("rehearse", {}))
            self.mix = traffic_mod.merged(self.mix, self.mix.get("rehearse", {}))
        self.workload, self.seed = workload, int(seed)
        self.seconds, self.trace, self.rehearse = float(seconds), trace, rehearse
        self.root = ROOT
        self.scratch = os.path.join(ROOT, ".chipbench_scratch", workload)
        self.split: Dict[str, float] = {}
        self._mark = process_start_time()
        self.started = self._mark
        self.reference = None
        self.device: Dict[str, Any] = {}
        # filled as the run goes; readers of per-layer metrics read these
        self.counters: Dict[str, Any] = {}
        self.end_to_end: Dict[str, float] = {}
        self.reduced: Optional[dict] = None
        self.trace_data: Optional[dict] = None

    def log(self, line: str, **fields) -> None:
        print(json.dumps({"line": line, **fields}), flush=True)

    def phase(self, name: str) -> None:
        """Close the set-up phase ``name``: everything since the last mark."""
        now = time.time()
        self.split[name] = self.split.get(name, 0.0) + now - self._mark
        self._mark = now

    @staticmethod
    def start_trace(trace_dir: str) -> None:
        """Start the profiler without the Python tracer and the HLO dump: a
        traced stretch holds 10^5 to 10^6 device events, and with every Python
        call beside them stopping and reading the trace took a quarter of an
        hour (PR 24). Host spans are the runtime's and the annotations'."""
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)

    def reduce_trace(self, trace_dir: str, span: Optional[str] = None) -> None:
        """Read the trace under ``trace_dir`` and reduce it, over the stretch
        from the first to the last host span called ``span`` where one is
        named. A rehearsal on a CPU has no device plane to reduce."""
        import shutil

        from chipbench import trace_reduce

        t0 = time.time()
        path = trace_reduce.find_xplane(trace_dir)
        size = os.path.getsize(path)
        self.trace_data = trace_reduce.read(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        t1 = time.time()
        if self.rehearse and not self.trace_data["devices"]:
            return
        spans = trace_reduce.host_spans(self.trace_data, span) if span else []
        self.reduced = trace_reduce.reduce(
            self.trace_data,
            window=(spans[0][0], spans[-1][1]) if spans else None)
        self.log("trace", xplane_bytes=size, read_s=t1 - t0,
                 reduce_s=time.time() - t1,
                 device_events=sum(len(d["ops"]) for d in
                                   self.trace_data["devices"].values()),
                 host_events=sum(map(len, self.trace_data["host"].values())))

    def load_reference(self):
        self.reference = load_by_path(
            os.path.join(self.cfg_dir, self.cfg["reference"]),
            "chipbench_reference_" + self.cfg["family"])
        return self.reference


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says. Set before the
    first compile of the process."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def watch_compiles() -> Dict[str, float]:
    """Count what JAX traces, lowers, compiles and finds in the persistent
    cache from here on (``jax.monitoring``): the set-up split says from it
    whether a run compiled or loaded."""
    import jax

    seen = {"cache_hits": 0, "cache_misses": 0, "trace_s": 0.0,
            "lower_s": 0.0, "backend_compile_s": 0.0}
    timed = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
             "/jax/core/compile/backend_compile_duration": "backend_compile_s"}

    def count(event, **_):
        if event.endswith("/compilation_cache/cache_hits"):
            seen["cache_hits"] += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            seen["cache_misses"] += 1

    def clock(event, seconds, **_):
        if event in timed:
            seen[timed[event]] += seconds

    jax.monitoring.register_event_listener(count)
    jax.monitoring.register_event_duration_secs_listener(clock)
    return seen


def device_block(chips: int, rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    if not rehearse and (d.platform != "tpu" or len(devices) < chips):
        print(f"chipbench: the cell needs {chips} TPU chip(s); JAX reports "
              f"{len(devices)} x {d.platform!r} (--rehearse runs toy sizes "
              f"anywhere, and its numbers are no results)", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def read_layer_metrics(run: Run) -> Dict[str, dict]:
    out = {}
    for metric in run.bench["per_layer"]:
        if not applies(metric, run.workload):
            continue
        reader = load_by_path(
            os.path.join(HERE, "layer_metrics", metric["name"] + ".py"),
            "chipbench_metric_" + metric["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on whatever backend is there; the "
                         "numbers are no results")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    run = Run(bench, args.workload, args.seed, args.seconds,
              bool(args.trace), args.rehearse)
    driver = importlib.import_module(f"chipbench.drivers.{run.mix['driver']}")
    # the system under test has to be there before anything is printed
    importlib.import_module("sparkflow_tpu")

    cache_dir = enable_compile_cache()
    compiles = watch_compiles()
    run.device = device_block(int(run.cell["chips"]), args.rehearse)
    run.load_reference()
    run.phase("imports")
    run.log("start", workload=run.workload, seed=run.seed,
            seconds=run.seconds, trace=int(run.trace), rehearse=run.rehearse,
            compile_cache_dir=cache_dir, device=run.device)

    state = driver.setup(run)
    gc.collect()
    gc.freeze()
    run.phase("other")
    compiled = dict(compiles)

    measured = driver.window(run, state)
    # set-up runs from the process's start to the measured window's start
    setup_s = measured["started"] - run.started
    # what JAX traced, compiled or loaded inside the window: nothing, or
    # set-up has not warmed every program the window drives
    in_window = {k: compiles[k] - compiled[k] for k in compiled}
    run.log("setup_split", setup_s=setup_s, **run.split, jax=compiled,
            jax_in_window=in_window)
    run.end_to_end = dict(measured["end_to_end"])
    run.end_to_end["setup_s"] = setup_s
    run.device["memory_peak_bytes"] = memory_peak_bytes()

    compared = driver.compare(run, state)
    correct = bool(compared) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())

    device = dict(run.device)
    result: Dict[str, Any] = {"correct": correct,
                              "attempted": int(measured["attempted"]),
                              "failed": int(measured["failed"])}
    if run.trace:
        result["metrics"] = read_layer_metrics(run)
        reduced = run.reduced or {}        # empty only in a CPU rehearsal
        device.update(busy_s=reduced.get("busy_s"),
                      window_s=reduced.get("window_s"))
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced.get("device_ops", []),
                               "idle_gaps": reduced.get("idle_gaps", [])}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]
                 if applies(m, run.workload)}
        result["metrics"] = {name: {"value": float(run.end_to_end[name]),
                                    "unit": unit}
                             for name, unit in units.items()}
        result["device"] = device
    result["compared"] = compared

    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
