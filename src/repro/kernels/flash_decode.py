"""Flash GQA decode attention — Pallas TPU kernel.

Single new token attending to a long KV cache:
  grid = (B, Hkv, Sk/block_k), k-axis sequential
  q tile    (G, hd)        VMEM  (all G q-heads of one kv head together —
                                  the (G, block_k) score tile feeds the MXU)
  k/v tiles (block_k, hd)  VMEM  streamed from the HBM-resident cache
  m/l/acc   scratch        VMEM  (fp32)

``kv_len`` (valid cache entries) arrives via scalar prefetch (SMEM) so the
same compiled kernel serves any fill level; blocks past kv_len are masked.
A scalar kv_len serves a synchronized batch; a (B,) vector serves
continuous batching, where every slot sits at its own fill level.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import compiler_params

NEG_INF = -1e30


def _kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            cap: float, scale: float, block_k: int, nk: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    kv_len = kvlen_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                  # (G, hd)
    k = k_ref[...].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if cap:
        s = cap * jnp.tanh(s / cap)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < kv_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_decode(q, k_cache, v_cache, kv_len, *, cap: float = 0.0,
                 scale: float = 0.0, block_k: int = 512,
                 interpret: bool = True):
    """q: (B,Hq,hd); caches: (B,Hkv,Sk,hd); kv_len: scalar or (B,) int32.

    Returns (B,Hq,hd)."""
    B, Hq, hd = q.shape
    Hkv, Sk = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = scale if scale else 1.0 / math.sqrt(hd)
    block_k = min(block_k, Sk)
    while Sk % block_k:
        block_k //= 2
    assert Sk % block_k == 0
    nk = Sk // block_k

    qf = q.reshape(B, Hkv, G, hd)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1),
                              (B,))

    kernel = functools.partial(_kernel, cap=cap, scale=scale,
                               block_k=block_k, nk=nk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((None, None, G, hd), lambda b, h, ki, kvl: (b, h, 0, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, ki, kvl: (b, h, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, ki, kvl: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G, hd),
                               lambda b, h, ki, kvl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kv_len, qf, k_cache, v_cache)
    return out.reshape(B, Hq, hd)
