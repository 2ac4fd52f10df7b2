"""The share of a step's tokens that carry loss: the program's counter
``masked_tokens`` (the noised positions that hold the mask token) over the
tokens of a step, mean over the steps of the counted calls, in %. The noise
law of ``blockdiff-seq4096`` gives 62.5 % in expectation.
Source: program_counter."""


def read(run):
    masked = (run.counters.get("model_metrics") or {}).get("masked_tokens")
    if masked is None:
        return None
    return 100.0 * masked / run.counters["tokens_per_step"]
