"""A configuration file, as the program and the reference see it.

The configuration file (``configs/<name>.json``) holds the model's
published keys at its top level (Hugging Face ``config.json`` names),
the engine settings under ``engine``, and a ``smoke`` block of overrides
for rehearsals on the CPU. This module turns it into the program's
``ModelConfig`` and makes the weights from the seed, on the device, in
one jitted call, in the type they are served in.
"""
from __future__ import annotations

import copy
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

SUPPORTED_ACT = {"silu": "silu_glu"}


@dataclass(frozen=True)
class Shape:
    """The sizes the benchmark's own arithmetic (FLOPs, bytes, the
    reference) needs; read from the configuration file alone."""
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


def smoke(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration with its ``smoke`` overrides applied (nested
    ``engine`` keys merge)."""
    out = copy.deepcopy(conf)
    over = conf.get("smoke", {})
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = {**out[k], **v}
        else:
            out[k] = v
    return out


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


def weight_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (the driver's exceed 32
    bits)."""
    word = int(np.random.default_rng([seed, 0x5EED]).integers(0, 2**31 - 1))
    return jax.random.PRNGKey(word)


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
