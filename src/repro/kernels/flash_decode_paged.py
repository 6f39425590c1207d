"""Paged flash GQA decode attention — Pallas TPU kernel.

Single new token per sequence attending to a KV cache scattered over
fixed-size blocks of a shared physical pool (serving/kvpool.py):

  grid = (B, Hkv, max_blocks), block axis sequential
  q tile    (G, hd)          VMEM (all G q-heads of one kv head)
  k/v tiles (block_size, hd) VMEM — fetched from the HBM-resident pool
                             at the PHYSICAL block the per-sequence
                             block table names for this logical block
  m/l/acc   scratch          VMEM (fp32 online softmax)

The block table (B, max_blocks) and per-sequence kv lengths (B,) arrive
via scalar prefetch (SMEM): the table is read inside the k/v BlockSpec
index_map, so the DMA engine walks each sequence's scattered blocks
while the same compiled kernel serves any table contents. Logical
blocks at or past ceil(kv_len / block_size) are masked out entirely
(their table entries are clamped sentinels pointing at an arbitrary
resident block — the fetch is harmless and the scores never survive
the kv_len mask).

Accumulation is sequential over the logical block axis — position
order — exactly like flash_decode's k-axis, just at block_size
granularity against non-contiguous storage.
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


def _kernel(tab_ref, kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, cap: float, scale: float, block_size: int,
            nb: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    kv_len = kvlen_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                  # (G, hd)
    k = k_ref[...].astype(jnp.float32)                  # (bs, hd)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if cap:
        s = cap * jnp.tanh(s / cap)
    kpos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < kv_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
    m_scr[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_paged(q, k_pages, v_pages, block_tab, kv_len, *,
                       cap: float = 0.0, scale: float = 0.0,
                       interpret: bool = True):
    """q: (B,Hq,hd); pages: (n_blocks,Hkv,bs,hd); block_tab: (B,mb)
    int32 (entries >= n_blocks are sentinels); kv_len: scalar or (B,)
    int32. Returns (B,Hq,hd)."""
    B, Hq, hd = q.shape
    n_blocks, Hkv, bs, _ = k_pages.shape
    G = Hq // Hkv
    mb = block_tab.shape[1]
    scale = scale if scale else 1.0 / math.sqrt(hd)

    qf = q.reshape(B, Hkv, G, hd)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1),
                              (B,))
    # sentinel entries must still name a resident block for the DMA;
    # kv_len masks every row they would contribute
    tab = jnp.clip(block_tab.astype(jnp.int32), 0, n_blocks - 1)

    kernel = functools.partial(_kernel, cap=cap, scale=scale,
                               block_size=bs, nb=mb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, mb),
        in_specs=[
            pl.BlockSpec((None, None, G, hd),
                         lambda b, h, j, tab, kvl: (b, h, 0, 0)),
            pl.BlockSpec((None, None, bs, hd),
                         lambda b, h, j, tab, kvl: (tab[b, j], h, 0, 0)),
            pl.BlockSpec((None, None, bs, hd),
                         lambda b, h, j, tab, kvl: (tab[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G, hd),
                               lambda b, h, j, tab, kvl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="flash_decode_paged",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tab, kv_len, qf, k_pages, v_pages)
    return out.reshape(B, Hq, hd)
