"""One reader per metric, found by the metric's name.

Each ``<name>.py`` defines ``read(run)``, which returns the metric's
value from what a run recorded (``bench.run.RunData``), or None where
the run holds nothing for it to read; the harness then leaves the
metric out of the result.
"""
