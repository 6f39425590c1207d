"""Sequential stabilized mLSTM recurrence — Pallas TPU kernel.

grid = (B * H, S/block_s), s-axis "arbitrary" (sequential); the per-head
state — C (hd, hd) matrix memory, n (1, hd) normalizer, m (1, 1) gate
stabilizer — lives in VMEM scratch across s-blocks with a fori_loop over
the block_s timesteps inside the kernel. Each step is an (hd, hd)
elementwise decay + rank-1 update plus an (hd,)·(hd,hd) matvec readout;
hd ≤ 128 keeps the whole state resident in VMEM.

Step order mirrors kernels/ref.mlstm_scan_ref exactly: the output divides
by max(|n·q|, exp(-m)), a catastrophically cancelled dot, so reassociating
the state updates is amplified without bound near zero denominators (see
models/xlstm.py). Initial state arrives as explicit inputs and the final
state is returned, so serving continues a sequence through the same
kernel (decode / chunked-prefill extend).

Mosaic stores no scalars to VMEM, so the stabilizer m and the gate
pre-activations are carried as (1, 1) vectors. Any S compiles: a long
sequence is padded to whole 8-aligned blocks (kernels/tiling.py) and
the pad steps are skipped, leaving the carried state untouched.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import compiler_params, fit_block, pad_axis


def _kernel(q_ref, k_ref, v_ref, i_ref, f_ref, c0_ref, n0_ref, m0_ref,
            h_ref, cf_ref, nf_ref, mf_ref, c_scr, n_scr, m_scr, *,
            block_s: int, ns: int, seq_len: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        c_scr[...] = c0_ref[...]
        n_scr[...] = n0_ref[...]
        m_scr[...] = m0_ref[...]

    def step(t, _):
        @pl.when(si * block_s + t < seq_len)            # skip pad steps
        def _step():
            row = pl.ds(t, 1)
            q_t = q_ref[row, :].astype(jnp.float32)     # (1, hd)
            ks_t = k_ref[row, :].astype(jnp.float32)    # pre-scaled k
            v_t = v_ref[row, :].astype(jnp.float32)
            # gates are (1, 1); widen them to (1, hd) rows before any
            # arithmetic, so every (hd, hd) broadcast below starts from a
            # row (Mosaic broadcasts one axis at a time)
            row_of = lambda g: jnp.broadcast_to(g, q_t.shape)
            i_t = row_of(i_ref[row, :])
            logf = jax.nn.log_sigmoid(row_of(f_ref[row, :]))
            m_prev = row_of(m_scr[...])
            m_new = jnp.maximum(logf + m_prev, i_t)     # (1, hd), lanes equal
            fw = jnp.exp(logf + m_prev - m_new)
            iw = jnp.exp(i_t - m_new)
            C = c_scr[...] * fw + iw * (ks_t.T * v_t)
            n = n_scr[...] * fw + iw * ks_t             # (1, hd)
            num = jnp.sum(C * q_t.T, axis=0, keepdims=True)  # (C^T q)^T
            m_new = m_new[:, :1]                        # (1, 1)
            den = jnp.maximum(
                jnp.abs(jnp.sum(n * q_t, axis=1, keepdims=True)),
                jnp.exp(-m_new))
            h_ref[row, :] = (num / den).astype(h_ref.dtype)
            c_scr[...] = C
            n_scr[...] = n
            m_scr[...] = m_new
        return _

    jax.lax.fori_loop(0, block_s, step, 0)

    @pl.when(si == ns - 1)
    def _finalize():
        cf_ref[...] = c_scr[...]
        nf_ref[...] = n_scr[...]
        mf_ref[...] = m_scr[...]


def mlstm_scan(q, k, v, i_pre, f_pre, state=None, *, scale: float = 0.0,
               block_s: int = 256, interpret: bool = True):
    """q, k, v: (B,H,S,hd); i_pre, f_pre: (B,H,S); state: optional
    (C (B,H,hd,hd), n (B,H,hd), m (B,H)) — models/xlstm.mlstm_state_init
    layout. Returns (h (B,H,S,hd) fp32, new_state)."""
    B, H, S, hd = q.shape
    scale = scale if scale else 1.0 / math.sqrt(hd)
    BH = B * H
    block_s, s_pad = fit_block(S, block_s, 8)
    ns = s_pad // block_s

    if state is None:
        state = (jnp.zeros((B, H, hd, hd), jnp.float32),
                 jnp.zeros((B, H, hd), jnp.float32),
                 jnp.full((B, H), -1e30, jnp.float32))
    C0, n0, m0 = state

    def rows(t, width):
        return pad_axis(t.reshape(BH, S, width).astype(jnp.float32), 1,
                        s_pad)

    qf, kf, vf = rows(q, hd), rows(k * scale, hd), rows(v, hd)
    i_f, f_f = rows(i_pre, 1), rows(f_pre, 1)
    c0 = C0.reshape(BH, hd, hd).astype(jnp.float32)
    n0f = n0.reshape(BH, 1, hd).astype(jnp.float32)
    m0f = m0.reshape(BH, 1, 1).astype(jnp.float32)

    kernel = functools.partial(_kernel, block_s=block_s, ns=ns, seq_len=S)
    seq = pl.BlockSpec((None, block_s, hd), lambda bh, s: (bh, s, 0))
    gate = pl.BlockSpec((None, block_s, 1), lambda bh, s: (bh, s, 0))
    cspec = pl.BlockSpec((None, hd, hd), lambda bh, s: (bh, 0, 0))
    nspec = pl.BlockSpec((None, 1, hd), lambda bh, s: (bh, 0, 0))
    mspec = pl.BlockSpec((None, 1, 1), lambda bh, s: (bh, 0, 0))
    h, cf, nf, mf = pl.pallas_call(
        kernel,
        name="mlstm_scan",
        grid=(BH, ns),
        in_specs=[seq, seq, seq, gate, gate, cspec, nspec, mspec],
        out_specs=[seq, cspec, nspec, mspec],
        out_shape=[jax.ShapeDtypeStruct((BH, s_pad, hd), jnp.float32),
                   jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32),
                   jax.ShapeDtypeStruct((BH, 1, hd), jnp.float32),
                   jax.ShapeDtypeStruct((BH, 1, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32),
                        pltpu.VMEM((1, hd), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.float32)],
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, i_f, f_f, c0, n0f, m0f)
    return (h[:, :S].reshape(B, H, S, hd),
            (cf.reshape(B, H, hd, hd), nf.reshape(B, H, hd),
             mf.reshape(B, H)))
