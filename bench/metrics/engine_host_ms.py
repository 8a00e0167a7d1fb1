"""Host self-time of ``DecodeEngine.step`` per decode segment, in ms.

The durations of the program's phase spans inside the traced window
(lifecycle, admission, ingest, segment, post), less the ``engine.wait``
spans nested in them (host reads that wait for the device), over the
``engine.segment`` spans that start in the window
(``bench.program_trace``). None where the run kept no program trace or
the program opened no segment span.
"""


def read(run):
    program = getattr(run, "program_trace", None)
    return None if program is None else program.engine_host_ms()
