"""The fullest held expert's load over the mean load of the held experts,
of the pairs a step routed here (the program's counter ``expert_load`` of a
fit's steps, ``TrainResult.metrics``), mean over the layers. 1 is an even
split; the grouped product's time follows the pairs, not this.
Source: program_counter."""


def read(run):
    load = (run.counters.get("model_metrics") or {}).get("expert_load")
    if not load:
        return None
    ratios = [max(layer) * len(layer) / max(sum(layer), 1e-9)
              for layer in load]
    return sum(ratios) / len(ratios)
