"""Device time of decode attention per decode step, in ms.

The device time of the ``jit__segment`` ops traced under the
``decode.attention`` named scope (``models/blocks.py``), over the decode
steps of the ``jit__segment`` runs that start in the traced window (runs
x ``segment_len``), from ``bench.program_trace``. None where the run
kept no program trace or no op carries the scope.
"""


def read(run):
    program = getattr(run, "program_trace", None)
    if program is None:
        return None
    return program.attention_ms_per_step(run.counters["segment_len"])
