"""Output tokens observed inside the window, over the window's length."""

from bench import stats


def read(run):
    return stats.rate(run.output_tokens, run.t0, run.t1)
