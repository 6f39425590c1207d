"""The operation counts behind the MFU metric (``models/qwen2.py``),
against values worked out by hand for one small shape."""
import os

from bench import spec

qwen2 = spec.model(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "qwen2")

# 2 layers, d_model 8, 2 query heads over 1 K/V head of 4, d_ff 16,
# vocabulary 10
S = qwen2.Shape(layers=2, d_model=8, n_heads=2, n_kv_heads=1, d_head=4,
                d_ff=16, vocab=10, tied=True, qkv_bias=False,
                rope_theta=1e4, norm_eps=1e-6)


def test_weights_per_token():
    # per layer: q 8x8, k and v 8x4 each, o 8x8, gate/up/down 3 x 8x16
    # = 64 + 64 + 64 + 384 = 576; two layers plus the 8x10 head
    assert qwen2.matmul_params(S) == 2 * 576 + 80


def test_per_token_counts():
    # 2 layers x 2 heads x 4 dims x (score + value) 2 multiply-adds
    assert qwen2.attn_pair_flops(S) == 64
    assert qwen2.decode_token_flops(S, 5) == 2 * 1232 + 64 * 5


def test_decode_flops_grow_with_the_context_only_by_attention():
    # one more key adds one (query, key) pair; the weights stay
    assert qwen2.decode_token_flops(S, 6) - qwen2.decode_token_flops(S, 5) \
        == qwen2.attn_pair_flops(S)
    assert qwen2.decode_token_flops(S, 0) == 2 * qwen2.matmul_params(S)
