"""The plain reference: the decoder's forward pass in float32 at the
highest matmul precision, written from the published description
(pre-norm RMSNorm decoder, rotary embeddings on the two halves of each
head, grouped-query causal attention, SiLU-gated MLP, QKV bias where the
configuration has it). It imports nothing of the program and takes
nothing the program made: only the benchmark's own weights and token
ids.

It runs in blocks of ``CHUNK`` positions against its own K/V buffers,
layer by layer inside one compiled program per configuration, so a
sequence of any length up to the buffer fits beside the weights.

``precision="int8"`` is the control: the same pass with every weight
matrix and its input activations quantized to int8 (symmetric, per
output channel and per row), the step below the configuration's
bfloat16. The comparison has to fail it.

RMSNorm scales are stored as offsets from one (the weights' layout):
the weight a published checkpoint would hold is ``1 + scale``.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Shape

CHUNK = 512
HIGHEST = jax.lax.Precision.HIGHEST


def _q8(a, axis):
    """Symmetric int8 quantization of ``a`` along ``axis``."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(a / scale).astype(jnp.int8), scale


def _matmul(x, w, precision):
    """x (n, k) f32 @ w (k, m) f32."""
    if precision == "float32":
        return jnp.matmul(x, w, precision=HIGHEST)
    xq, xs = _q8(x, 1)
    wq, ws = _q8(w, 0)
    acc = jax.lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    """x (h, n, hd) at positions pos (n,)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("shape", "precision"),
                   donate_argnames=("kbuf", "vbuf"))
def forward_chunk(weights, kbuf, vbuf, tokens, offset, *, shape: Shape,
                  precision: str):
    """Positions [offset, offset + CHUNK) of one sequence: writes their
    K/V into the buffers (L, Hkv, S, hd) and returns their logits
    (CHUNK, V) in float32."""
    s = shape
    f32 = lambda a: a.astype(jnp.float32)
    mm = functools.partial(_matmul, precision=precision)
    n = tokens.shape[0]
    pos = offset + jnp.arange(n)
    x = f32(jnp.take(weights["embed"], tokens, axis=0))
    G = s.n_heads // s.n_kv_heads
    S = kbuf.shape[2]
    visible = jnp.arange(S)[None, :] <= pos[:, None]          # (n, S)

    def layer(x, xs):
        p, kb, vb = xs
        a = p["attn"]
        h = _rms(x, p["norm1"]["scale"], s.norm_eps)
        q, k, v = mm(h, f32(a["wq"])), mm(h, f32(a["wk"])), \
            mm(h, f32(a["wv"]))
        if s.qkv_bias:
            q, k, v = q + f32(a["bq"]), k + f32(a["bk"]), v + f32(a["bv"])
        heads = lambda t, m: t.reshape(n, m, s.d_head).transpose(1, 0, 2)
        q = _rope(heads(q, s.n_heads), pos, s.rope_theta)
        k = _rope(heads(k, s.n_kv_heads), pos, s.rope_theta)
        v = heads(v, s.n_kv_heads)
        kb = jax.lax.dynamic_update_slice(kb, k, (0, offset, 0))
        vb = jax.lax.dynamic_update_slice(vb, v, (0, offset, 0))
        kg = jnp.repeat(kb, G, axis=0)                         # (H, S, hd)
        vg = jnp.repeat(vb, G, axis=0)
        sc = jnp.einsum("hnd,hsd->hns", q, kg, precision=HIGHEST) \
            / np.sqrt(s.d_head)
        sc = jnp.where(visible[None], sc, -jnp.inf)
        o = jnp.einsum("hns,hsd->hnd", jax.nn.softmax(sc, axis=-1), vg,
                       precision=HIGHEST)
        o = o.transpose(1, 0, 2).reshape(n, s.n_heads * s.d_head)
        x = x + mm(o, f32(a["wo"]))
        h = _rms(x, p["norm2"]["scale"], s.norm_eps)
        m = p["mlp"]
        g = mm(h, f32(m["w_gate"]))
        x = x + mm(jax.nn.silu(g) * mm(h, f32(m["w_up"])), f32(m["w_down"]))
        return x, (kb, vb)

    layers = weights["segments"][0][0]
    x, (kbuf, vbuf) = jax.lax.scan(layer, x, (layers, kbuf, vbuf))
    x = _rms(x, weights["final_norm"]["scale"], s.norm_eps)
    head = (weights["embed"].T if s.tied else weights["lm_head"])
    return mm(x, f32(head)), kbuf, vbuf


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


def sequence_gaps(weights, shape: Shape, seq: List[int],
                  targets: List[Tuple[int, int]], buf_len: int,
                  control: bool = False) -> Tuple[float, float]:
    """Widest gap of the served tokens ``targets`` -- (position whose
    logits predict it, token) pairs -- of one sequence, and, with
    ``control``, the widest gap of the int8 control's own choices at
    every position of the sequence (else 0): the control need not
    decode."""
    if len(seq) > buf_len:
        raise ValueError(f"sequence of {len(seq)} > buffer {buf_len}")
    by_pos = dict(targets)
    s = shape
    zeros = lambda: jnp.zeros((s.layers, s.n_kv_heads, buf_len, s.d_head),
                              jnp.float32)
    bufs = {"float32": [zeros(), zeros()]}
    if control:
        bufs["int8"] = [zeros(), zeros()]
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
        for prec, (kb, vb) in bufs.items():
            logits[prec], kb, vb = forward_chunk(
                weights, kb, vb, jnp.asarray(ids), off, shape=s,
                precision=prec)
            bufs[prec] = [kb, vb]
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
