"""99th percentile of the inter-token latency, in ms, over every output
token that reached the client inside the window (a request's first token
excepted): the time since the request's previous observation with
tokens, over the tokens this one brought."""

from bench import stats


def read(run):
    p = stats.percentile(stats.itl_samples(run.records), 99)
    return None if p is None else p * 1e3
