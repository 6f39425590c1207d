"""What every model module's plain reference shares (``models/*.py``
may import these): the key its weights are drawn from, float32 matmuls
at the highest precision with their int8 control, RMSNorm, rotary
embeddings, and the loop that runs a reference over one sequence in
blocks of ``CHUNK`` positions and reads the gaps. None of it imports
the program or takes anything the program made.

``precision="int8"`` is the control: every weight matrix and its input
activations quantized to int8 (symmetric, per output channel and per
row), the step below the configurations' bfloat16. The comparison has
to fail it.

RMSNorm scales are stored as offsets from one (the weights' layout):
the weight a published checkpoint would hold is ``1 + scale``.
"""
from __future__ import annotations

from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 512
HIGHEST = jax.lax.Precision.HIGHEST


def weight_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (the driver's exceed 32
    bits)."""
    word = int(np.random.default_rng([seed, 0x5EED]).integers(0, 2**31 - 1))
    return jax.random.PRNGKey(word)


def _q8(a, axis):
    """Symmetric int8 quantization of ``a`` along ``axis``."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(a / scale).astype(jnp.int8), scale


def matmul(x, w, precision):
    """x (n, k) f32 @ w (k, m) f32, in float32 or as the int8 control."""
    if precision == "float32":
        return jnp.matmul(x, w, precision=HIGHEST)
    xq, xs = _q8(x, 1)
    wq, ws = _q8(w, 0)
    acc = jax.lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(jnp.float32))


def rope(x, pos, theta):
    """x (h, n, hd) at positions pos (n,), rotating the two halves of
    each head."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@jax.jit
def _gaps(ref_logits, served, valid, ctrl_logits, in_seq):
    """How far the served tokens' reference logits lie below the
    reference's best (at the served positions), and the same for the
    tokens the control puts first (at every position of the
    sequence)."""
    best = jnp.max(ref_logits, axis=-1)
    pick = lambda t: jnp.take_along_axis(ref_logits, t[:, None], 1)[:, 0]
    gap = jnp.where(valid, best - pick(served), 0.0)
    top = jnp.argmax(ctrl_logits, axis=-1)
    ctrl = jnp.where(in_seq, best - pick(top), 0.0)
    return jnp.max(gap), jnp.max(ctrl)


def chunked_gaps(step: Callable, buffers: Callable, seq: List[int],
                 targets: List[Tuple[int, int]], buf_len: int,
                 control: bool = False) -> Tuple[float, float]:
    """Widest gap of the served tokens ``targets`` -- (position whose
    logits predict it, token) pairs -- of one sequence, and, with
    ``control``, the widest gap of the int8 control's own choices at
    every position of the sequence (else 0): the control need not
    decode.

    ``step(bufs, ids, offset, precision)`` runs a model's reference over
    positions [offset, offset + CHUNK) and returns their logits (CHUNK,
    V) in float32 with its caches updated; ``buffers()`` gives a fresh
    set of those caches for ``buf_len`` positions."""
    if len(seq) > buf_len:
        raise ValueError(f"sequence of {len(seq)} > buffer {buf_len}")
    by_pos = dict(targets)
    bufs = {"float32": buffers()}
    if control:
        bufs["int8"] = buffers()
    last = len(seq) - 1 if control else max(by_pos)
    worst = ctrl_worst = 0.0
    for off in range(0, last + 1, CHUNK):
        ids = np.zeros(CHUNK, np.int32)
        part = seq[off:off + CHUNK]
        ids[:len(part)] = part
        served = np.zeros(CHUNK, np.int32)
        valid = np.zeros(CHUNK, bool)
        for i in range(CHUNK):
            if off + i in by_pos:
                served[i], valid[i] = by_pos[off + i], True
        logits = {}
        for prec in bufs:
            logits[prec], bufs[prec] = step(bufs[prec], jnp.asarray(ids),
                                            off, prec)
        if not (valid.any() or control):
            continue
        in_seq = np.arange(CHUNK) < len(seq) - off
        g, c = _gaps(logits["float32"], jnp.asarray(served),
                     jnp.asarray(valid),
                     logits.get("int8", logits["float32"]),
                     jnp.asarray(in_seq))
        worst = max(worst, float(g))
        ctrl_worst = max(ctrl_worst, float(c))
    return worst, (ctrl_worst if control else 0.0)
