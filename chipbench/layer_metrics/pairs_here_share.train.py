"""The share of a step's (token, expert) pairs that landed on the experts
held here (the program's counters ``expert_load`` and ``pairs_routed``), mean
over the layers, in %. 16 of 128 experts under an even router hold 12.5 %.
Source: program_counter."""


def read(run):
    m = run.counters.get("model_metrics") or {}
    if not m.get("expert_load") or not m.get("pairs_routed"):
        return None
    here = sum(sum(layer) for layer in m["expert_load"]) / len(m["expert_load"])
    return 100.0 * here / m["pairs_routed"]
