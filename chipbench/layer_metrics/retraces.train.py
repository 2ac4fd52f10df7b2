"""Traces of the train step inside the window (``Trainer.recompile_report``
after each timed call, summed). Source: program_counter."""


def read(run):
    return run.counters.get("retraces")
