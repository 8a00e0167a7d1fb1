"""Model FLOP/s utilisation of the decode segment program, in %.

The operations of the tokens the segments emitted inside the window (the
model's matmuls and head, and the attention each token's own context
needs) over the device time of ``jit__segment`` times the chip's peak.
A token's context is its prompt and the tokens before it, itself
included; the first token of a request comes from admission, not from a
segment.
"""


def read(run):
    if run.trace is None:
        return None
    secs = run.trace.module_s(["jit__segment"])
    if secs <= 0:
        return None
    lm = run.work()
    n_tok, ctx = 0, 0
    for r in run.records:
        first, last = max(r.c_t0, 1), r.c_t1
        if last > first:
            n_tok += last - first
            ctx += lm.range_ctx_sum(first, last, len(r.prompt))
    if n_tok == 0:
        return None
    flops = (n_tok * (lm.matmul_flops(run.conf) + lm.head_flops(run.conf))
             + lm.attention_flops(run.conf, ctx, n_tok))
    return 100.0 * flops / (secs * run.peak["bf16_flops_per_s"])
