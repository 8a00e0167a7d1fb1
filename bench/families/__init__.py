"""One module per configuration family, found by name.

A configuration file names its family under ``"family"``; a file
without the key is ``"dense"``. The harness asks the family module
``bench/families/<family>.py`` (``spec.family``) for everything that
depends on the architecture, so that a configuration of another
architecture is added as new files and ``BENCHMARK.json`` entries only.

A family module defines:

- ``model_config(conf)``: the program's ``ModelConfig`` for the file,
  refusing keys the program cannot serve as the file states them;
- ``make(conf, key)``: the weight tree, in the layout the program takes,
  from one ``jax.random`` key; ``weights.make_params`` folds the seed
  into the key and runs it as one jitted call on the device;
- ``grows_kv(conf)``: whether any layer's decode state grows with the
  context, so that the warm-up also admits each bucket of prompt length
  (``run.warm_lengths``);
- ``REFERENCE``: the name of its plain reference,
  ``bench/reference/<REFERENCE>.py``;
- ``WORK``: the name of its operation and byte counts,
  ``bench/work/<WORK>.py``;
- ``SMOKE``: the configuration keys the CPU tests shrink, and their values.

The reference module imports nothing of the program, takes the weights
``make`` made and nothing the program made, and defines

    served_gaps(params, conf, prompt, served, control=False) -> np.ndarray

one float per served token: how far its logit lies below the best logit
of the float32 reference at its position (0 where it is the reference's
own choice). With ``control``, the token at each position is instead
the one the control ranks first: the reference computed one precision
step below the configuration's (float8 e4m3 operands for bfloat16
weights). ``check.compare`` holds the widest gap to the cell's limit.

The work module counts what the algorithm needs, whatever computes it
(a multiply and an add are two operations; padding, inactive slots and
work done twice are not counted):

    matmul_flops(conf)                     weight matmuls, one token
    head_flops(conf)                       the output head, one token
    attention_flops(conf, ctx_sum, n_tok)  attention of n_tok tokens
                                           whose contexts add to ctx_sum
    weight_bytes(conf)                     weights one decode step reads
    decode_state_bytes(conf, ctx_sum, n_tok)
                                           decode state those tokens move
    range_ctx_sum(first, last, offset)     sum of offset + i, first <= i < last

``RunData.work()`` returns the configuration's work module to readers.
"""
