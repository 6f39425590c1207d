"""The operation counts behind the MFU metric, against values worked
out by hand for one small shape."""
from bench import flops
from bench.model import Shape

# 2 layers, d_model 8, 2 query heads over 1 K/V head of 4, d_ff 16,
# vocabulary 10
S = Shape(layers=2, d_model=8, n_heads=2, n_kv_heads=1, d_head=4, d_ff=16,
          vocab=10, tied=True, qkv_bias=False, rope_theta=1e4,
          norm_eps=1e-6)


def test_weights_per_token():
    # per layer: q 8x8, k and v 8x4 each, o 8x8, gate/up/down 3 x 8x16
    # = 64 + 64 + 64 + 384 = 576; two layers plus the 8x10 head
    assert flops.matmul_params(S) == 2 * 576 + 80


def test_per_token_counts():
    # 2 layers x 2 heads x 4 dims x (score + value) 2 multiply-adds
    assert flops.attn_pair_flops(S) == 64
    assert flops.decode_token_flops(S, 5) == 2 * 1232 + 64 * 5


def test_decode_flops_grow_with_the_context_only_by_attention():
    # one more key adds one (query, key) pair; the weights stay
    assert flops.decode_token_flops(S, 6) - flops.decode_token_flops(S, 5) \
        == flops.attn_pair_flops(S)
    assert flops.decode_token_flops(S, 0) == 2 * flops.matmul_params(S)
