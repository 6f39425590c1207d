"""In-graph sharding annotations (``with_sharding_constraint`` helpers).

GSPMD propagates shardings well through matmuls but loses them at
head-boundary reshapes when head counts are not divisible by the model
axis (hymba 25q/5kv, gemma2 8q/4kv vs model=16). The dry-run analysis
showed attention then running at *global* batch, replicated per chip —
a 16 TiB/chip temp for hymba train_4k. ``constrain_attn`` pins the
batch/head layout explicitly; every spec dim is divisibility-guarded so
the same code lowers on any mesh (including the single-device test mesh,
where it is a no-op).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax._src.mesh import thread_resources as _tr


def _mesh():
    m = _tr.env.physical_mesh
    return None if m.empty else m


def _fit(mesh, dim: int, axes):
    """Largest prefix of `axes` (present in mesh) whose product divides dim."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    axes = tuple(a for a in axes if a in mesh.axis_names)
    while axes:
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if dim % n == 0:
            return axes if len(axes) > 1 else axes[0]
        axes = axes[:-1]
    return None


UNCONSTRAINED = "__unconstrained__"


def constrain(x, *per_dim):
    """with_sharding_constraint with divisibility-guarded per-dim axes.

    per_dim: one axis-name / tuple / None / UNCONSTRAINED per array dim
    (UNCONSTRAINED leaves that dim to GSPMD instead of pinning it
    replicated). No-op outside a mesh context (unit tests, single-device
    benches).
    """
    mesh = _mesh()
    if mesh is None:
        return x
    spec = P(*[P.UNCONSTRAINED if ax is UNCONSTRAINED
               else _fit(mesh, d, ax) for d, ax in zip(x.shape, per_dim)])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def attn_batch_head_axes(mesh, batch: int, n_q_heads: int, n_kv_heads: int):
    """Pick (batch_axes, q_head_axes, kv_head_axes) for attention internals.

    Preference order (refined after the first production sweep — the
    blanket "spread batch over everything" rule regressed wide/deep
    models by up to 15x, see EXPERIMENTS.md §Prod-profile):
      1. q heads divisible by `model` -> Megatron TP: q heads over model,
         kv heads over model too when they divide, else replicated (GQA
         kv is small); batch over data. Zero/cheap resharding.
      2. q heads NOT shardable but batch divisible by data*model ->
         batch over both axes (attention fully data-parallel; pays one
         activation reshard in/out — only wins when heads are stuck,
         e.g. hymba's 25q/5kv).
      3. otherwise: pin ONLY the batch dim (keeps GSPMD from replicating
         attention at global batch — the original hymba bug) and leave
         every other dim UNCONSTRAINED so seq-sharded-KV plans survive.
    """
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if "model" not in mesh.axis_names:
        return dp, None, None
    m = mesh.shape["model"]
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if n_q_heads % m == 0:
        kv_ax = ("model",) if n_kv_heads % m == 0 else None
        return dp, ("model",), kv_ax
    if batch % (n_dp * m) == 0:
        return dp + ("model",), dp + ("model",), dp + ("model",)
    return dp, UNCONSTRAINED, UNCONSTRAINED


def constrain_attn(q, k, v):
    """Pin (B, H, S, hd) sharding for attention inputs.

    Returns (q, k, v, pinned). pinned=False means GSPMD keeps full
    freedom (callers use this to gate optimizations that assume KV is
    chip-local, e.g. banded window slicing).
    """
    mesh = _mesh()
    if mesh is None:
        return q, k, v, True       # single device: trivially local
    bax, qhax, kvhax = attn_batch_head_axes(mesh, q.shape[0], q.shape[1],
                                            k.shape[1])
    if qhax is UNCONSTRAINED:      # batch-only pin: GSPMD keeps seq freedom
        U = UNCONSTRAINED
        q = constrain(q, bax, U, U, U)
        k = constrain(k, bax, U, U, U)
        v = constrain(v, bax, U, U, U)
        return q, k, v, False      # KV may still be seq-sharded
    if bax and "model" in bax:     # batch-spread mode: heads stay local
        q = constrain(q, bax, None, None, None)
        k = constrain(k, bax, None, None, None)
        v = constrain(v, bax, None, None, None)
        return q, k, v, True
    q = constrain(q, bax, qhax, None, None)
    k = constrain(k, bax, kvhax, None, None)
    v = constrain(v, bax, kvhax, None, None)
    return q, k, v, True


def constrain_seq(x, seq_axis: str):
    """Pin (B, S, d) activations to batch-over-data, seq-over-`seq_axis`.

    Sequence-parallel prefill: sharding only the *input tokens* is a hint
    GSPMD discards (tokens are tiny — it re-shards immediately); pinning
    the residual stream per layer is what actually holds the layout.
    """
    mesh = _mesh()
    if mesh is None or not seq_axis or seq_axis == "none":
        return x
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return constrain(x, dp if x.shape[0] > 1 else None, seq_axis, None)


def constrain_attn_out(out, n_kv_heads: int):
    """Pin (B, Hq, S, hd) sharding of the attention output (pre out-proj)."""
    mesh = _mesh()
    if mesh is None:
        return out
    bax, qhax, _ = attn_batch_head_axes(mesh, out.shape[0], out.shape[1],
                                        n_kv_heads)
    if qhax is UNCONSTRAINED:
        U = UNCONSTRAINED
        return constrain(out, bax, U, U, U)
    if bax and "model" in bax:
        return constrain(out, bax, None, None, None)
    return constrain(out, bax, qhax, None, None)
