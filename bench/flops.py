"""Operations the served work needs, from the configuration's shapes
and the live lengths the harness recorded -- never from what a kernel
happens to compute, so the counts stay the same whatever implements
them. A multiply-add is 2 FLOPs.
"""
from __future__ import annotations

from bench.model import Shape


def matmul_params(s: Shape) -> int:
    """Weights every token multiplies by: the layers' projections and
    MLP (SiLU-GLU: gate, up, down) and the output head."""
    qd, kvd = s.n_heads * s.d_head, s.n_kv_heads * s.d_head
    per_layer = s.d_model * qd + 2 * s.d_model * kvd + qd * s.d_model \
        + 3 * s.d_model * s.d_ff
    return s.layers * per_layer + s.d_model * s.vocab


def attn_pair_flops(s: Shape) -> int:
    """FLOPs of one (query, key) pair over all heads and layers: the
    score and the value product, 2 multiply-adds per head dimension."""
    return 4 * s.layers * s.n_heads * s.d_head


def decode_token_flops(s: Shape, kv_len: int) -> int:
    """Model FLOPs of one decoded token attending ``kv_len`` keys."""
    return 2 * matmul_params(s) + attn_pair_flops(s) * kv_len
