"""Read what a cell's limits are set from, on the chip: ``python3 -m
chipbench.control --workload <cell> --seeds 1,2,...,12 --control-seeds 1,2,3``.

For every seed the cell's driver reads the numbers of the comparison from a
sound run of the program, and for the control's seeds from the control too:
the reference computed in the nearest precision below the configuration's
(int8 matrix products for bf16), put in the program's place. A limit lies
above the sound runs' largest reading and below the control's smallest
(PERF.md section 2 records both). The benchmark's own runs never run the
control; ``chipbench/tests/test_runs.py`` keeps it at a toy size.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

from chipbench import run as run_mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    with open(os.path.join(run_mod.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    run_mod.enable_compile_cache()
    runs = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run = run_mod.Run(bench, args.workload, seed, 0.0, False,
                          args.rehearse)
        run.device = run_mod.device_block(int(run.cell["chips"]),
                                          args.rehearse)
        run.load_reference()
        runs.append(run)
    driver = importlib.import_module(
        f"chipbench.drivers.{runs[0].mix['driver']}")
    driver.readings(runs, {int(s) for s in args.control_seeds.split(",")
                           if s})
    return 0


if __name__ == "__main__":
    sys.exit(main())
