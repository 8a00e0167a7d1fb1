"""FLOP and byte counts at qwen3-0.6b's published shapes, worked by hand.

Per layer: q 1024x2048, k and v 1024x1024 each, o 2048x1024 and the
SwiGLU MLP 3 x 1024x3072: 15,728,640 weights, 440,401,920 over 28
layers, two operations each. Head: 1024 x 151936.
"""

from bench import spec


def conf(backend="linear"):
    return spec.load_json(spec.BENCH_DIR / "configs"
                          / f"qwen3-0.6b-{backend}.json")


def test_dense_lm_counts():
    lm = spec.load_module("work", "dense_lm")
    c = conf()
    assert lm.matmul_flops(c) == 2 * 440_401_920
    assert lm.head_flops(c) == 2 * 155_582_464
    # linear: 16 heads x (4 x 128^2 + 4 x 128) x 28 layers per token
    assert lm.attention_flops(c, ctx_sum=10**9, n_tokens=3) == \
        3 * 28 * 16 * (4 * 16384 + 512)
    # softmax: 4 x 16 heads x 128 per position attended, 28 layers
    assert lm.attention_flops(conf("softmax"), ctx_sum=1000, n_tokens=3) \
        == 28 * 4 * 16 * 128 * 1000


def test_context_sums():
    lm = spec.load_module("work", "dense_lm")
    # tokens 1..3 of a 10-token prompt attend to 11, 12 and 13 positions
    assert lm.range_ctx_sum(1, 4, 10) == 36
    assert lm.range_ctx_sum(5, 5, 10) == 0


def test_dense_lm_bytes():
    lm = spec.load_module("work", "dense_lm")
    # weights, bf16: 440,401,920 + head 155,582,464 + norms 28 x (2 x 1024
    # + 2 x 128) + 1024 = 596,049,920 parameters
    assert lm.weight_bytes(conf()) == 2 * 596_049_920
    # linear: 28 layers x 16 heads x (128^2 + 128) f32, read and written
    assert lm.decode_state_bytes(conf(), ctx_sum=10**9, n_tokens=3) == \
        3 * 2 * 28 * 16 * 16_512 * 4
    # softmax: 28 layers x (k and v) x 8 heads x 128 bf16 = 114,688 bytes
    # per position, 1000 read and 3 written
    assert lm.decode_state_bytes(conf("softmax"), ctx_sum=1000,
                                 n_tokens=3) == 114_688 * 1003
