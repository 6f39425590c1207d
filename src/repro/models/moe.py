"""Mixture-of-Experts FFN with capacity-bounded dispatch.

Two dispatch implementations:

* ``einsum``   — GShard-style one-hot dispatch/combine einsums. This is the
  paper-era baseline: it lowers cleanly to all-to-all under GSPMD when the
  expert axis is sharded over the ``model`` mesh axis, but it spends real
  MXU flops on the one-hot matmuls (visible in cost_analysis — the roofline
  §Perf loop flips to ``gather`` to recover them).
* ``gather``   — take/segment-matmul dispatch: tokens are gathered into a
  dense (E, C, d) buffer with jnp.take and combined with a scatter-free
  weighted sum. Far fewer flops; GSPMD still partitions the expert matmuls.

Router top-k runs in fp32. The auxiliary load-balance loss follows
Switch/GShard: E * sum_e(f_e * p_e).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig, MoEConfig
from repro.models.layers import dense_init, mlp_init, mlp

DISPATCH_MODE = "einsum"   # module-level default; overridable per-call


def moe_init(rng, cfg: ModelConfig, dtype=jnp.bfloat16):
    mcfg = cfg.moe
    ks = jax.random.split(rng, 6)
    d, dff, E = cfg.d_model, mcfg.d_expert, mcfg.n_experts
    p = {
        "router": dense_init(ks[0], (d, E), scale=0.1, dtype=jnp.float32),
        # Expert FFNs stacked on a leading expert axis (sharded over `model`).
        "w_gate": (jax.random.normal(ks[1], (E, d, dff), jnp.float32)
                   / math.sqrt(d)).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, dff), jnp.float32)
                 / math.sqrt(d)).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, dff, d), jnp.float32)
                   / math.sqrt(dff)).astype(dtype),
    }
    if mcfg.dense_residual_ff:
        p["dense_residual"] = mlp_init(ks[4], d, mcfg.dense_residual_ff,
                                       cfg.mlp_act, dtype)
    if mcfg.shared_expert_ff:
        p["shared_expert"] = mlp_init(ks[5], d, mcfg.shared_expert_ff,
                                      cfg.mlp_act, dtype)
    return p


def capacity(mcfg: MoEConfig, n_tokens: int) -> int:
    from repro.common.perf import get_flags
    cf = get_flags().moe_capacity_factor or mcfg.capacity_factor
    c = int(math.ceil(cf * mcfg.top_k * n_tokens / mcfg.n_experts))
    return max(8, -(-c // 8) * 8)      # round up to a multiple of 8


def router_topk(router_w, x, mcfg: MoEConfig, backend=None):
    """x: (B,S,d) -> (weights (B,S,k), idx (B,S,k) int32, probs (B,S,E)).

    With a non-reference kernel backend (and no active mesh), the fused
    softmax+top-k Pallas kernel selects the experts; probs are still
    computed here — the load-balance aux loss needs the full (B,S,E)
    distribution either way."""
    from repro.kernels import backend as KB
    logits = x.astype(jnp.float32) @ router_w            # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    be = KB.get_backend(backend)
    if be.name != "reference" and KB.mesh_local():
        B, S, E = logits.shape
        w, idx = be.router_topk(logits.reshape(B * S, E), mcfg.top_k)
        return (w.reshape(B, S, mcfg.top_k),
                idx.reshape(B, S, mcfg.top_k), probs)
    w, idx = jax.lax.top_k(probs, mcfg.top_k)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    return w, idx, probs


def load_balance_loss(probs, idx, mcfg: MoEConfig):
    E = mcfg.n_experts
    onehot = jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32)   # top-1 choice
    f = jnp.mean(onehot, axis=(0, 1))
    p = jnp.mean(probs, axis=(0, 1))
    return E * jnp.sum(f * p)


def _expert_ffn(p, xe, act: str):
    """xe: (E, C, d) -> (E, C, d); expert-stacked matmuls."""
    if act.endswith("_glu"):
        gate = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
        up = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
        h = (jax.nn.silu(gate) if act == "silu_glu"
             else jax.nn.gelu(gate, approximate=True)) * up
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, p["w_up"]),
                        approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def _dispatch_einsum(p, x, w, idx, mcfg, act, pin: bool = False):
    """GShard one-hot dispatch. x: (B,S,d).

    pin=True applies the GShard-canonical sharding constraints so the
    token exchange lowers to all-to-all over (data <-> model) instead of
    GSPMD's replicate+all-reduce fallback (see EXPERIMENTS.md §Perf,
    kimi-prefill iteration 2).
    """
    from repro.distributed.annotate import constrain
    dp = ("pod", "data")
    c9 = (lambda t, *ax: constrain(t, *ax)) if pin else (lambda t, *ax: t)
    B, S, d = x.shape
    E = mcfg.n_experts
    C = capacity(mcfg, S)
    # Position of each (token, k) inside its expert's buffer.
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)             # (B,S,k,E)
    pos = jnp.cumsum(onehot.reshape(B, S * mcfg.top_k, E), axis=1) - 1
    pos = pos.reshape(B, S, mcfg.top_k, E)
    in_cap = (pos < C) & (onehot > 0)
    # dispatch (B,S,E,C) / combine (B,S,E,C)
    pos_oh = jax.nn.one_hot(pos, C, dtype=x.dtype) * in_cap[..., None]
    dispatch = jnp.einsum("bske,bskec->bsec", onehot.astype(x.dtype),
                          pos_oh * 1.0)
    combine = jnp.einsum("bsk,bske,bskec->bsec", w.astype(x.dtype),
                         onehot.astype(x.dtype), pos_oh)
    dispatch = c9(dispatch, dp, None, "model", None)
    combine = c9(combine, dp, None, "model", None)
    # Group axis = batch. expert_in: (E, B, C, d)
    expert_in = c9(jnp.einsum("bsec,bsd->ebcd", dispatch, c9(x, dp, None, None)),
                   "model", dp, None, None)
    Eb = expert_in.reshape(E, B * C, d)
    out = c9(_expert_ffn(p, Eb, act).reshape(E, B, C, d),
             "model", dp, None, None)
    y = c9(jnp.einsum("bsec,ebcd->bsd", combine, out), dp, None, None)
    return y


def _dispatch_gather(p, x, w, idx, mcfg, act, pin: bool = False):
    """Sort-free gather dispatch: flat take into (E, C, d) buffers.

    pin=True: expert buffers constrained to the `model` axis inside the
    per-batch vmap (spmd_axis_name keeps the batch dim on `data`), so the
    token exchange lowers to all-to-all instead of the combine-gather
    all-reduce (EXPERIMENTS.md §Perf kimi iteration 5).
    """
    from repro.distributed.annotate import constrain
    B, S, d = x.shape
    E, K = mcfg.n_experts, mcfg.top_k
    C = capacity(mcfg, S)

    def per_batch(xb, wb, ib):
        # xb (S,d), wb (S,K), ib (S,K)
        flat_e = ib.reshape(-1)                                   # (S*K,)
        oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = (jnp.cumsum(oh, axis=0) - 1)[jnp.arange(S * K), flat_e]
        keep = pos < C
        slot = jnp.where(keep, flat_e * C + pos, E * C)           # overflow slot
        tok = jnp.repeat(jnp.arange(S), K)
        # Gather tokens into expert buffers via scatter into (E*C+1, ).
        buf_tok = jnp.zeros((E * C + 1,), jnp.int32).at[slot].set(tok)
        buf_valid = jnp.zeros((E * C + 1,), jnp.bool_).at[slot].set(keep)
        xe = jnp.take(xb, buf_tok[:-1], axis=0) * buf_valid[:-1, None]
        xe = xe.reshape(E, C, d)
        if pin:
            xe = constrain(xe, "model", None, None)
        ye = _expert_ffn(p, xe, act)
        if pin:
            ye = constrain(ye, "model", None, None)
        ye = ye.reshape(E * C, d)
        # Combine: each (token,k) reads back its slot.
        contrib = jnp.take(jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)]),
                           slot, axis=0)
        contrib = contrib * (wb.reshape(-1, 1).astype(ye.dtype) * keep[:, None])
        return jnp.sum(contrib.reshape(S, K, d), axis=1)

    vm = (jax.vmap(per_batch, spmd_axis_name="data") if pin
          else jax.vmap(per_batch))
    return vm(x, w.astype(x.dtype), idx)


def _dispatch_shard_map(p, x, w, idx, mcfg, act):
    """Expert-parallel dispatch as an explicit shard_map over `model`.

    Written for the TPU production mesh after the GSPMD-only iterations
    (EXPERIMENTS.md §Perf kimi 1-5) plateaued: each model shard owns
    E/m contiguous experts, gathers its assigned tokens *locally* (x is
    replicated across `model`), runs the expert FFNs, and the combine is
    a single bf16 psum of (B,S,d) per layer — no (B,S,E,C) one-hot masks
    and no dispatch matmuls at all. Falls back to `gather` without a mesh.
    """
    from repro.distributed.annotate import _mesh
    from jax.sharding import PartitionSpec as P
    mesh = _mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return _dispatch_gather(p, x, w, idx, mcfg, act)
    m_size = mesh.shape["model"]
    E, K = mcfg.n_experts, mcfg.top_k
    if E % m_size != 0:
        return _dispatch_gather(p, x, w, idx, mcfg, act)
    el = E // m_size
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    B, S, d = x.shape
    bax = dp if (B % n_dp == 0 and B > 1) else ()
    bspec = bax if bax else None

    def shard_fn(p_loc, xb, wb, ib):
        j = jax.lax.axis_index("model")
        Bl, Sl, _ = xb.shape
        N = Bl * Sl
        C = capacity(mcfg, N)
        xf = xb.reshape(N, d)
        ib_loc = ib.reshape(N * K) - j * el          # local expert ids
        wf = wb.reshape(N * K)
        mine = (ib_loc >= 0) & (ib_loc < el)
        e_loc = jnp.where(mine, ib_loc, el)          # el = overflow expert
        oh = jax.nn.one_hot(e_loc, el + 1, dtype=jnp.int32)
        pos = (jnp.cumsum(oh, axis=0) - 1)[jnp.arange(N * K), e_loc]
        keep = mine & (pos < C)
        slot = jnp.where(keep, e_loc * C + pos, el * C)
        tok = jnp.repeat(jnp.arange(N), K)
        buf_tok = jnp.zeros((el * C + 1,), jnp.int32).at[slot].set(tok)
        buf_valid = jnp.zeros((el * C + 1,), jnp.bool_).at[slot].set(keep)
        xe = (jnp.take(xf, buf_tok[:-1], axis=0)
              * buf_valid[:-1, None]).reshape(el, C, d)
        ye = _expert_ffn(p_loc, xe, act).reshape(el * C, d)
        contrib = jnp.take(
            jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)]), slot, axis=0)
        contrib = contrib * (wf[:, None].astype(ye.dtype) * keep[:, None])
        y = jnp.sum(contrib.reshape(Bl, Sl, K, d), axis=2)
        return jax.lax.psum(y.astype(xb.dtype), "model")

    p_exp = {k: p[k] for k in ("w_gate", "w_up", "w_down")}
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P("model"), p_exp),
                  P(bspec, None, None), P(bspec, None, None),
                  P(bspec, None, None)),
        out_specs=P(bspec, None, None), check_vma=False)
    return fn(p_exp, x, w, idx)


def moe_ffn(p, x, cfg: ModelConfig, dispatch: str = None, backend=None):
    """Full MoE FFN layer. Returns (y, aux_loss)."""
    from repro.common.perf import get_flags
    mcfg = cfg.moe
    mode = dispatch or get_flags().moe_dispatch
    w, idx, probs = router_topk(p["router"], x, mcfg, backend=backend)
    aux = load_balance_loss(probs, idx, mcfg) * mcfg.aux_loss_weight
    # Dispatch pins only help bulk (train/prefill) token exchange; for
    # decode (S=1) they forced per-step all-to-alls that regressed the
    # first production sweep by ~40% — let GSPMD choose there.
    pin = get_flags().moe_constraint == "auto" and x.shape[1] > 1
    if mode == "einsum":
        y = _dispatch_einsum(p, x, w, idx, mcfg, cfg.mlp_act, pin=pin)
    elif mode == "gather":
        y = _dispatch_gather(p, x, w, idx, mcfg, cfg.mlp_act, pin=pin)
    elif mode == "shard_map":
        if x.shape[1] > 1:
            y = _dispatch_shard_map(p, x, w, idx, mcfg, cfg.mlp_act)
        else:
            # decode (S=1): the broadcast+psum exchange costs more than a
            # single token's FFN — use the plain einsum path, unpinned
            y = _dispatch_einsum(p, x, w, idx, mcfg, cfg.mlp_act, pin=False)
    else:
        raise ValueError(mode)
    if "dense_residual" in p:
        y = y + mlp(p["dense_residual"], x, cfg.mlp_act)
    if "shared_expert" in p:
        y = y + mlp(p["shared_expert"], x, cfg.mlp_act)
    return y, aux
