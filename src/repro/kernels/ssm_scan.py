"""Selective SSM scan — Pallas TPU kernel.

TPU adaptation of the CUDA selective-scan: the recurrence is blocked as
  grid = (B, d_inner/block_d, S/block_s), s-axis sequential
with the (block_d, n) state carried in VMEM scratch across s-blocks and a
sequential fori_loop over the block_s timesteps inside the kernel (the
(block_d, n) update is a VPU-wide elementwise op; n=16 keeps the state
tile tiny, so the kernel is bandwidth-bound on dt/x streaming, which is
the roofline-optimal regime for SSMs).

The scan starts from an explicit initial state ``h0`` and returns the
final state alongside the outputs, so serving can continue a sequence
(decode / chunked-prefill extend) through the same kernel.

Any d_inner and S compile: d_inner is tiled in multiples of 128 lanes
and S in multiples of 8 rows (kernels/tiling.py), zero-padded. A zero
dt makes a pad step exactly the identity on the state (exp(0)=1 decay,
zero input), so padded channels and trailing pad steps leave h_last
untouched; their outputs are sliced off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import compiler_params, fit_block, pad_axis


def _kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, hf_ref,
            h_scr, *, block_s: int, ns: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = h0_ref[...]

    A = a_ref[...]                                      # (bd, n)

    def step(t, h):
        dt_t = dt_ref[t, :].astype(jnp.float32)         # (bd,)
        x_t = x_ref[t, :].astype(jnp.float32)
        b_t = b_ref[t, :].astype(jnp.float32)           # (n,)
        c_t = c_ref[t, :].astype(jnp.float32)
        a = jnp.exp(dt_t[:, None] * A)                  # (bd, n)
        h = a * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_ref[t, :] = jnp.sum(h * c_t[None, :], axis=1).astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_s, step, h_scr[...])

    @pl.when(si == ns - 1)
    def _finalize():
        hf_ref[...] = h_scr[...]


def ssm_scan(dt, x, B_, C_, A, h0=None, *, block_d: int = 256,
             block_s: int = 256, interpret: bool = True):
    """dt, x: (B,S,di); B_, C_: (B,S,n); A: (di,n); h0: optional initial
    state (B,di,n). Returns (y (B,S,di) fp32, h_last (B,di,n) fp32)."""
    Bsz, S, di = x.shape
    n = A.shape[-1]
    block_d, d_pad = fit_block(di, block_d, 128)
    block_s, s_pad = fit_block(S, block_s, 8)
    nd, ns = d_pad // block_d, s_pad // block_s
    if h0 is None:
        h0 = jnp.zeros((Bsz, di, n), jnp.float32)

    def pad_seq(t, d=True):
        t = pad_axis(t, 1, s_pad)
        return pad_axis(t, 2, d_pad) if d else t

    kernel = functools.partial(_kernel, block_s=block_s, ns=ns)
    y, h_last = pl.pallas_call(
        kernel,
        name="ssm_scan",
        grid=(Bsz, nd, ns),
        in_specs=[
            pl.BlockSpec((None, block_s, block_d),
                         lambda b, d, s: (b, s, d)),
            pl.BlockSpec((None, block_s, block_d),
                         lambda b, d, s: (b, s, d)),
            pl.BlockSpec((None, block_s, n), lambda b, d, s: (b, s, 0)),
            pl.BlockSpec((None, block_s, n), lambda b, d, s: (b, s, 0)),
            pl.BlockSpec((block_d, n), lambda b, d, s: (d, 0)),
            pl.BlockSpec((None, block_d, n), lambda b, d, s: (b, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_s, block_d),
                         lambda b, d, s: (b, s, d)),
            pl.BlockSpec((None, block_d, n), lambda b, d, s: (b, d, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((Bsz, s_pad, d_pad), jnp.float32),
                   jax.ShapeDtypeStruct((Bsz, d_pad, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pad_seq(dt), pad_seq(x), pad_seq(B_, d=False), pad_seq(C_, d=False),
      pad_axis(A.astype(jnp.float32), 0, d_pad),
      pad_axis(h0.astype(jnp.float32), 1, d_pad))
    return y[:, :S, :di], h_last[:, :di]
