"""The share of the experts' row buffers that a step's kernels and row copies
visited (the program's counters ``expert_rows_live``, the rows of the tiles in
use by layer, and ``expert_rows_bound``, the buffers' rows: sized for the
worst routing), mean over the layers, in %. 100 when every expert is held and
every tile full. ``None`` for a program without the counters. Source:
program_counter."""


def read(run):
    m = run.counters.get("model_metrics") or {}
    live, bound = m.get("expert_rows_live"), m.get("expert_rows_bound")
    if not live or not bound:
        return None
    return 100.0 * sum(live) / len(live) / bound
