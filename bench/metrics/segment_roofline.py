"""The decode segment program's share of its roofline, in %.

The least time the segments' work needs on the chip, over the device
time of ``jit__segment`` inside the window. The least time is the larger
of the bytes the algorithm must move at the chip's HBM bandwidth and its
operations at the chip's bf16 peak. Bytes: every weight once per decode
step the segments ran, and per token emitted its slot's attention state
(linear: the f32 state and key sum, read and written) or the KV rows its
context attends plus the one it adds (softmax). Operations: as
``segment_mfu`` counts them. Inactive slots, padding and copies are not
work, so an implementation that moves the state twice reads lower.
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
    c = run.counters
    steps = c["segments"] * c["segment_len"]
    nbytes = (steps * lm.weight_bytes(run.conf)
              + lm.decode_state_bytes(run.conf, ctx, n_tok))
    flops = (n_tok * (lm.matmul_flops(run.conf) + lm.head_flops(run.conf))
             + lm.attention_flops(run.conf, ctx, n_tok))
    least = max(nbytes / run.peak["hbm_bytes_per_s"],
                flops / run.peak["bf16_flops_per_s"])
    return 100.0 * least / secs
