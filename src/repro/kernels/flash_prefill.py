"""Flash prefill attention — Pallas TPU kernel.

Online-softmax over KV blocks with explicit VMEM tiling:
  grid = (B * Hq, Sq/block_q, Sk/block_k), k-axis "arbitrary" (sequential)
  q tile    (block_q, hd)   VMEM
  k/v tiles (block_k, hd)   VMEM
  m/l/acc   scratch         VMEM (fp32)

Causal, sliding-window and logit-softcap variants are compile-time flags.
block_q/block_k default to 128/256 — multiples of the 128-wide MXU tile,
with the (block_q, block_k) score tile + accumulators well inside the
~16 MiB/core VMEM budget for hd ≤ 256.

Any Sq and Sk compile: a sequence axis longer than its block is padded
to a whole number of 8-aligned blocks (kernels/tiling.py). Padded key
rows are masked; padded query rows are sliced off the output.

GQA: the kv head index is derived from the q head index in the BlockSpec
index maps (hq // group).

``q_offset`` (the absolute position of q[0], for chunked-prefill extend
against a pre-filled cache) arrives via scalar prefetch (SMEM) so one
compiled kernel serves any continuation point; cache rows at or beyond
q_offset + Sq are masked by the causal term.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import compiler_params, fit_block, pad_axis

NEG_INF = -1e30


def _kernel(qoff_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int, cap: float, scale: float,
            block_q: int, block_k: int, nk: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_off = qoff_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                 # (bq, hd)
    k = k_ref[...].astype(jnp.float32)                 # (bk, hd)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if cap:
        s = cap * jnp.tanh(s / cap)

    qpos = q_off + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if kv_len:                                         # padded key rows
        mask = mask & (kpos < kv_len)
    if causal:
        mask = mask & (kpos <= qpos)
    if window:
        mask = mask & (qpos - kpos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_prefill(q, k, v, *, causal: bool = True, window: int = 0,
                  cap: float = 0.0, scale: float = 0.0, q_offset=0,
                  block_q: int = 128, block_k: int = 256,
                  interpret: bool = True):
    """q: (B,Hq,Sq,hd); k,v: (B,Hkv,Sk,hd) -> (B,Hq,Sq,hd).

    q_offset: absolute position of q[0] (python int or traced scalar)."""
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = scale if scale else 1.0 / math.sqrt(hd)
    block_q, sq_pad = fit_block(Sq, block_q, 8)
    block_k, sk_pad = fit_block(Sk, block_k, 8)
    nq, nk = sq_pad // block_q, sk_pad // block_k

    qf = pad_axis(q.reshape(B * Hq, Sq, hd), 1, sq_pad)
    kf = pad_axis(k.reshape(B * Hkv, Sk, hd), 1, sk_pad)
    vf = pad_axis(v.reshape(B * Hkv, Sk, hd), 1, sk_pad)
    q_off = jnp.asarray(q_offset, jnp.int32).reshape((1,))

    kernel = functools.partial(
        _kernel, causal=causal, window=window, cap=cap, scale=scale,
        block_q=block_q, block_k=block_k, nk=nk,
        kv_len=Sk if sk_pad != Sk else 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, hd),
                         lambda bh, qi, ki, qo: (bh, qi, 0)),
            pl.BlockSpec((None, block_k, hd),
                         lambda bh, qi, ki, qo, G=G: (bh // G, ki, 0)),
            pl.BlockSpec((None, block_k, hd),
                         lambda bh, qi, ki, qo, G=G: (bh // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd),
                               lambda bh, qi, ki, qo: (bh, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="flash_prefill",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, sq_pad, hd), q.dtype),
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_off, qf, kf, vf)
    return out[:, :Sq].reshape(B, Hq, Sq, hd)
