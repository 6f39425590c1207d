"""Fused MoE router (softmax + top-k) — Pallas TPU kernel.

grid = (T / block_t,); each instance handles a (block_t, E) tile of router
logits resident in VMEM and produces normalized top-k weights + expert ids
via k iterative argmax passes (k ≤ 8, E ≤ 512 — the (block_t, E) tile and
its fp32 softmax fit VMEM comfortably).

Capacity masking is cross-token (a global cumsum) and stays outside the
kernel, in models/moe.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import compiler_params, fit_block, pad_axis

NEG_INF = -1e30


def _kernel(x_ref, w_ref, i_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)                 # (bt, E)
    bt, E = x.shape
    m = jnp.max(x, axis=1, keepdims=True)
    p = jnp.exp(x - m)
    probs = p / jnp.sum(p, axis=1, keepdims=True)

    work = probs
    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, E), 1)
    tot = jnp.zeros((bt, 1), jnp.float32)
    ws, ids = [], []
    for _ in range(k):
        best = jnp.max(work, axis=1, keepdims=True)
        best_idx = jnp.argmax(work, axis=1)            # (bt,)
        ws.append(best)
        ids.append(best_idx[:, None])
        tot = tot + best
        work = jnp.where(cols == best_idx[:, None], NEG_INF, work)
    w = jnp.concatenate(ws, axis=1) / jnp.maximum(tot, 1e-9)
    i = jnp.concatenate(ids, axis=1)
    w_ref[...] = w
    i_ref[...] = i.astype(jnp.int32)


def moe_router_topk(logits, k: int, *, block_t: int = 256,
                    interpret: bool = True):
    """logits: (T, E) -> (weights (T,k) fp32, idx (T,k) int32)."""
    T, E = logits.shape
    # pad rows route independently and are sliced off
    block_t, t_pad = fit_block(T, block_t, 8)
    nt = t_pad // block_t

    kernel = functools.partial(_kernel, k=k)
    w, i = pl.pallas_call(
        kernel,
        name="moe_router",
        grid=(nt,),
        in_specs=[pl.BlockSpec((block_t, E), lambda t: (t, 0))],
        out_specs=[pl.BlockSpec((block_t, k), lambda t: (t, 0)),
                   pl.BlockSpec((block_t, k), lambda t: (t, 0))],
        out_shape=[jax.ShapeDtypeStruct((t_pad, k), jnp.float32),
                   jax.ShapeDtypeStruct((t_pad, k), jnp.int32)],
        compiler_params=compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(pad_axis(logits, 0, t_pad))
    return w[:T], i[:T]
