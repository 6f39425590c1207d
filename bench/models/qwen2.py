"""``model_type`` "qwen2": a dense pre-norm decoder (Qwen1.5, Qwen2) --
grouped-query causal attention with QKV bias where the configuration
has it, rotary embeddings on the two halves of each head, a SiLU-gated
MLP, RMSNorm, tied or untied output head.

What a model module gives the benchmark (``spec.model`` loads it by the
configuration file's ``model_type``):

* ``Shape.of(conf)`` -- the sizes the benchmark's own arithmetic needs,
  read from the configuration file alone (Hugging Face ``config.json``
  names at its top level);
* ``model_config(conf)`` -- the program's ``ModelConfig``;
* ``make_weights(shape, seed, eos_id=)`` -- every weight from the seed,
  on the device, in one jitted call, in the type it is served in;
* ``sequence_gaps(weights, shape, seq, targets, buf_len, control=)`` --
  the float32 reference over one sequence and its int8 control
  (``reference.chunked_gaps``);
* ``decode_token_flops(shape, kv_len)`` -- the model FLOPs of one
  decoded token.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import (HIGHEST, chunked_gaps, matmul, rms, rope,
                             weight_key)

SUPPORTED_ACT = {"silu": "silu_glu"}


@dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    rope_theta: float
    norm_eps: float

    @classmethod
    def of(cls, conf: Dict[str, Any]) -> "Shape":
        return cls(layers=conf["num_hidden_layers"],
                   d_model=conf["hidden_size"],
                   n_heads=conf["num_attention_heads"],
                   n_kv_heads=conf["num_key_value_heads"],
                   d_head=conf.get("head_dim", conf["hidden_size"]
                                   // conf["num_attention_heads"]),
                   d_ff=conf["intermediate_size"],
                   vocab=conf["vocab_size"],
                   tied=bool(conf["tie_word_embeddings"]),
                   qkv_bias=bool(conf.get("attention_bias", False)),
                   rope_theta=float(conf["rope_theta"]),
                   norm_eps=float(conf["rms_norm_eps"]))


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for this configuration file."""
    from repro.common.config import ModelConfig
    s = Shape.of(conf)
    if conf["hidden_act"] not in SUPPORTED_ACT:
        raise ValueError(f"hidden_act {conf['hidden_act']!r} is not served")
    if conf.get("torch_dtype", "bfloat16") != "bfloat16":
        raise ValueError("the engine serves bfloat16 weights only")
    return ModelConfig(
        name=conf["name"], family="dense", n_layers=s.layers,
        d_model=s.d_model, n_heads=s.n_heads, n_kv_heads=s.n_kv_heads,
        d_head=s.d_head, d_ff=s.d_ff, vocab_size=s.vocab,
        segments=((("full",), s.layers),), rope_theta=s.rope_theta,
        qkv_bias=s.qkv_bias, mlp_act=SUPPORTED_ACT[conf["hidden_act"]],
        tie_embeddings=s.tied, norm_eps=s.norm_eps)


def make_weights(shape: Shape, seed: int, *, eos_id: int):
    """All weights from ``seed`` in one jitted call on the default
    device, bf16, in the program's parameter layout.

    Each matrix is normal with std 1/sqrt(fan_in); the embedding has std
    0.02 as a trained one does. Norm scales and QKV biases are drawn too
    (not left at their zero start), so a path that drops them shows.
    The output row of ``eos_id`` is zero: its logit is then never the
    largest, and every request serves exactly the number of tokens the
    traffic drew for it."""
    s = shape
    L, d, qd, kvd = s.layers, s.d_model, s.n_heads * s.d_head, \
        s.n_kv_heads * s.d_head

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def mat(shape_, fan_in):
            return (jax.random.normal(next(ks), shape_, jnp.float32)
                    / np.sqrt(fan_in)).astype(jnp.bfloat16)

        def small(shape_, std):
            return (jax.random.normal(next(ks), shape_, jnp.float32)
                    * std).astype(jnp.bfloat16)

        embed = small((s.vocab, d), 0.02)
        attn = {"wq": mat((L, d, qd), d), "wk": mat((L, d, kvd), d),
                "wv": mat((L, d, kvd), d), "wo": mat((L, qd, d), qd)}
        if s.qkv_bias:
            attn.update(bq=small((L, qd), 0.02), bk=small((L, kvd), 0.02),
                        bv=small((L, kvd), 0.02))
        layer = {"norm1": {"scale": small((L, d), 0.1)},
                 "attn": attn,
                 "norm2": {"scale": small((L, d), 0.1)},
                 "mlp": {"w_gate": mat((L, d, s.d_ff), d),
                         "w_up": mat((L, d, s.d_ff), d),
                         "w_down": mat((L, s.d_ff, d), s.d_ff)}}
        params = {"embed": embed,
                  "final_norm": {"scale": small((d,), 0.1)},
                  "segments": [[layer]]}
        if s.tied:
            params["embed"] = embed.at[eos_id].set(0)
        else:
            params["lm_head"] = mat((d, s.vocab), d).at[:, eos_id].set(0)
        return params

    return jax.jit(build)(weight_key(seed))


@functools.partial(jax.jit, static_argnames=("shape", "precision"),
                   donate_argnames=("kbuf", "vbuf"))
def forward_chunk(weights, kbuf, vbuf, tokens, offset, *, shape: Shape,
                  precision: str):
    """The reference over positions [offset, offset + CHUNK) of one
    sequence, in float32 at the highest matmul precision (or as the
    int8 control), layer by layer inside one compiled program: writes
    their K/V into the buffers (L, Hkv, S, hd) and returns their logits
    (CHUNK, V) in float32."""
    s = shape
    f32 = lambda a: a.astype(jnp.float32)
    mm = functools.partial(matmul, precision=precision)
    n = tokens.shape[0]
    pos = offset + jnp.arange(n)
    x = f32(jnp.take(weights["embed"], tokens, axis=0))
    G = s.n_heads // s.n_kv_heads
    S = kbuf.shape[2]
    visible = jnp.arange(S)[None, :] <= pos[:, None]          # (n, S)

    def layer(x, xs):
        p, kb, vb = xs
        a = p["attn"]
        h = rms(x, p["norm1"]["scale"], s.norm_eps)
        q, k, v = mm(h, f32(a["wq"])), mm(h, f32(a["wk"])), \
            mm(h, f32(a["wv"]))
        if s.qkv_bias:
            q, k, v = q + f32(a["bq"]), k + f32(a["bk"]), v + f32(a["bv"])
        heads = lambda t, m: t.reshape(n, m, s.d_head).transpose(1, 0, 2)
        q = rope(heads(q, s.n_heads), pos, s.rope_theta)
        k = rope(heads(k, s.n_kv_heads), pos, s.rope_theta)
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
        h = rms(x, p["norm2"]["scale"], s.norm_eps)
        m = p["mlp"]
        g = mm(h, f32(m["w_gate"]))
        x = x + mm(jax.nn.silu(g) * mm(h, f32(m["w_up"])), f32(m["w_down"]))
        return x, (kb, vb)

    layers = weights["segments"][0][0]
    x, (kbuf, vbuf) = jax.lax.scan(layer, x, (layers, kbuf, vbuf))
    x = rms(x, weights["final_norm"]["scale"], s.norm_eps)
    head = (weights["embed"].T if s.tied else weights["lm_head"])
    return mm(x, f32(head)), kbuf, vbuf


def sequence_gaps(weights, shape: Shape, seq: List[int],
                  targets: List[Tuple[int, int]], buf_len: int,
                  control: bool = False) -> Tuple[float, float]:
    """``reference.chunked_gaps`` with this model's reference and its
    K/V buffers."""
    s = shape

    def buffers():
        zeros = lambda: jnp.zeros(
            (s.layers, s.n_kv_heads, buf_len, s.d_head), jnp.float32)
        return zeros(), zeros()

    def step(bufs, ids, offset, precision):
        logits, kb, vb = forward_chunk(weights, *bufs, ids, offset,
                                       shape=s, precision=precision)
        return logits, (kb, vb)

    return chunked_gaps(step, buffers, seq, targets, buf_len, control)


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
    """Model FLOPs of one decoded token attending ``kv_len`` keys,
    from the configuration's shapes and the live length -- never from
    what a kernel happens to compute. A multiply-add is 2 FLOPs."""
    return 2 * matmul_params(s) + attn_pair_flops(s) * kv_len
