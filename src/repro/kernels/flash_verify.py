"""Flash speculative-verify attention — Pallas TPU kernels.

Speculative decoding scores the K drafted tokens (plus the carried last
token) of every slot in ONE target forward: W = K+1 query rows per
sequence attend a (partially) filled KV cache, causally at per-slot
offsets. Two storage layouts share one kernel body:

  flash_verify        dense caches (B, Hkv, Sk, hd) — the k-axis grid /
                      tiling mirrors flash_decode exactly;
  flash_verify_paged  block pools (n_blocks, Hkv, bs, hd) + a per-slot
                      block table — the block-axis grid / DMA walk
                      mirrors flash_decode_paged exactly.

  grid = (B, Hkv, Sk/block_k | max_blocks), k axis sequential
  q tile    (G*W, hd)        VMEM (all G q-heads x W verify rows of one
                                   kv head; the (G*W, block_k) score
                                   tile feeds the MXU)
  k/v tiles (block_k|bs, hd) VMEM
  m/l/acc   scratch          VMEM (fp32 online softmax)

Per-slot ``kv_len`` (valid rows AFTER the verify write — query row w
sits at absolute position kv_len - W + w) arrives via scalar prefetch,
the paged variant additionally prefetching the block table into its
BlockSpec index_map like flash_decode_paged.

Mirroring matters beyond performance: every fp32 op in the online
softmax is row-independent and accumulated over the SAME k-partition as
the decode kernels, so verify row w is bitwise identical to what
flash_decode/flash_decode_paged would produce for a single token at
that position — the property that makes speculative decoding emit
exactly the non-speculative token stream (DESIGN.md §Speculative
decoding).
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


def _kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, cap: float, scale: float, block_k: int, nk: int, W: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    kv_len = kvlen_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                  # (G*W, hd)
    k = k_ref[...].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if cap:
        s = cap * jnp.tanh(s / cap)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # row r of the tile is verify position w = r % W of q-head r // W;
    # its absolute position is kv_len - W + w (causal per row)
    w = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % W
    mask = kpos <= kv_len - W + w
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


def _kernel_paged(tab_ref, kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                  l_scr, acc_scr, *, cap, scale, block_k, nk, W):
    # the block table is consumed by the BlockSpec index_maps only; the
    # kernel body is identical to the dense variant
    _kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            cap=cap, scale=scale, block_k=block_k, nk=nk, W=W)


def flash_verify(q, k_cache, v_cache, kv_len, *, cap: float = 0.0,
                 scale: float = 0.0, block_k: int = 512,
                 interpret: bool = True):
    """q: (B,Hq,W,hd); caches: (B,Hkv,Sk,hd); kv_len: scalar or (B,)
    int32 — valid rows after the verify write. Returns (B,Hq,W,hd)."""
    B, Hq, W, hd = q.shape
    Hkv, Sk = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = scale if scale else 1.0 / math.sqrt(hd)
    # identical k-partition derivation to flash_decode: the per-row
    # accumulation order must match the decode kernel bit for bit
    block_k = min(block_k, Sk)
    while Sk % block_k:
        block_k //= 2
    assert Sk % block_k == 0
    nk = Sk // block_k

    qf = q.reshape(B, Hkv, G * W, hd)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1),
                              (B,))

    kernel = functools.partial(_kernel, cap=cap, scale=scale,
                               block_k=block_k, nk=nk, W=W)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((None, None, G * W, hd),
                         lambda b, h, ki, kvl: (b, h, 0, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, ki, kvl: (b, h, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, ki, kvl: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G * W, hd),
                               lambda b, h, ki, kvl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G * W, 1), jnp.float32),
            pltpu.VMEM((G * W, 1), jnp.float32),
            pltpu.VMEM((G * W, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="flash_verify",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G * W, hd), q.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kv_len, qf, k_cache, v_cache)
    return out.reshape(B, Hq, W, hd)


def flash_verify_paged(q, k_pages, v_pages, block_tab, kv_len, *,
                       cap: float = 0.0, scale: float = 0.0,
                       interpret: bool = True):
    """q: (B,Hq,W,hd); pages: (n_blocks,Hkv,bs,hd); block_tab: (B,mb)
    int32 (entries >= n_blocks are sentinels); kv_len: scalar or (B,)
    int32 — valid rows after the verify write. Returns (B,Hq,W,hd)."""
    B, Hq, W, hd = q.shape
    n_blocks, Hkv, bs, _ = k_pages.shape
    G = Hq // Hkv
    mb = block_tab.shape[1]
    scale = scale if scale else 1.0 / math.sqrt(hd)

    qf = q.reshape(B, Hkv, G * W, hd)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1),
                              (B,))
    # sentinel entries must still name a resident block for the DMA;
    # the per-row kv_len/causal mask kills every row they contribute
    tab = jnp.clip(block_tab.astype(jnp.int32), 0, n_blocks - 1)

    kernel = functools.partial(_kernel_paged, cap=cap, scale=scale,
                               block_k=bs, nk=mb, W=W)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, mb),
        in_specs=[
            pl.BlockSpec((None, None, G * W, hd),
                         lambda b, h, j, tab, kvl: (b, h, 0, 0)),
            pl.BlockSpec((None, None, bs, hd),
                         lambda b, h, j, tab, kvl: (tab[b, j], h, 0, 0)),
            pl.BlockSpec((None, None, bs, hd),
                         lambda b, h, j, tab, kvl: (tab[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G * W, hd),
                               lambda b, h, j, tab, kvl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G * W, 1), jnp.float32),
            pltpu.VMEM((G * W, 1), jnp.float32),
            pltpu.VMEM((G * W, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="flash_verify_paged",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G * W, hd), q.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tab, kv_len, qf, k_pages, v_pages)
    return out.reshape(B, Hq, W, hd)
