"""Pluggable attention/op kernel backend registry.

Every compute hot-spot in the model stack (attention prefill/extend,
cache decode, MoE router top-k, selective-SSM scan, mLSTM recurrence)
dispatches through a named :class:`KernelBackend`:

  * ``reference`` — the pure-jnp paths (layers.attention's chunked
    GSPMD-friendly attention, lax.top_k routing, associative-scan SSM,
    chunkwise mLSTM). Always available, partitionable under pjit.
  * ``pallas``    — the hand-tiled Pallas TPU kernels in this package,
    compiled on a TPU. On CPU they run under ``interpret=True``
    (bit-accurate, slow), so the same selection is testable there; any
    other platform is refused.

Selection, in precedence order:

  1. per-call  — ``backend="pallas"`` threaded through the model API
     (engine/prefill/decode_step/... all take it);
  2. scoped    — ``with use_backend("pallas"): ...``;
  3. global    — ``PerfFlags.kernel_backend`` (the ``--perf`` CLI knob).

Model-level call sites treat any backend other than ``reference`` as "use
the backend's kernels when the op is expressible" and keep the jnp path
for the rest (e.g. under an active device mesh, where GSPMD owns
partitioning — Pallas kernels are chip-local).
"""
from __future__ import annotations

import contextlib
import inspect
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple, Union

import jax

from repro.kernels import ref as R
from repro.kernels.flash_decode import flash_decode
from repro.kernels.flash_decode_paged import flash_decode_paged
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.flash_verify import flash_verify, flash_verify_paged
from repro.kernels.mlstm_scan import mlstm_scan
from repro.kernels.moe_router import moe_router_topk
from repro.kernels.ssm_scan import ssm_scan


@dataclass(frozen=True)
class KernelBackend:
    """One implementation of the kernel op vocabulary.

    All ops share the reference signatures (see kernels/ref.py):
      attention(q, k, v, *, causal, window, cap, scale, q_offset)
      decode_attention(q, k_cache, v_cache, kv_len, *, cap, scale)
      paged_decode_attention(q, k_pages, v_pages, block_tab, kv_len, *,
                             cap, scale)
      verify_attention(q (B,Hq,W,hd), k_cache, v_cache, kv_len, *,
                       cap, scale)
      paged_verify_attention(q (B,Hq,W,hd), k_pages, v_pages, block_tab,
                             kv_len, *, cap, scale)
      router_topk(logits (T,E), k) -> (weights (T,k) fp32, idx (T,k) i32)
      selective_scan(dt, x, B_, C_, A, h0) -> (y, h_last)
      mlstm_scan(q, k, v, i_pre, f_pre, state, *, scale) -> (h, state)
    """
    name: str
    attention: Callable
    decode_attention: Callable
    paged_decode_attention: Callable
    verify_attention: Callable
    paged_verify_attention: Callable
    router_topk: Callable
    selective_scan: Callable
    mlstm_scan: Callable


#: The declared call surface of every op, as ``op -> (positional arg
#: names, keyword-only arg names)``. This is the machine-readable form
#: of the signature block in :class:`KernelBackend`'s docstring: model
#: call sites may pass exactly these arguments to any backend, so every
#: registered implementation must *accept* the full surface (extra
#: parameters are fine only when they carry defaults — e.g. the
#: reference ``attention``'s ``kv_len``). ``register_backend`` enforces
#: this at import time; ``repro.analysis.backend_check`` re-checks it
#: (plus registry completeness) under lint as RL301–RL303.
OP_SURFACE: Dict[str, Tuple[Tuple[str, ...], Tuple[str, ...]]] = {
    "attention": (("q", "k", "v"),
                  ("causal", "window", "cap", "scale", "q_offset")),
    "decode_attention": (("q", "k_cache", "v_cache", "kv_len"),
                         ("cap", "scale")),
    "paged_decode_attention": (
        ("q", "k_pages", "v_pages", "block_tab", "kv_len"),
        ("cap", "scale")),
    "verify_attention": (("q", "k_cache", "v_cache", "kv_len"),
                         ("cap", "scale")),
    "paged_verify_attention": (
        ("q", "k_pages", "v_pages", "block_tab", "kv_len"),
        ("cap", "scale")),
    "router_topk": (("logits", "k"), ()),
    "selective_scan": (("dt", "x", "B_", "C_", "A", "h0"), ()),
    "mlstm_scan": (("q", "k", "v", "i_pre", "f_pre", "state"), ("scale",)),
}

OPS: Tuple[str, ...] = tuple(OP_SURFACE)


class BackendContractError(TypeError):
    """A registered implementation cannot serve the declared op surface
    (missing/renamed parameters, or extras without defaults)."""


def check_op_signature(op: str, impl: Callable) -> Optional[str]:
    """Return a defect description if ``impl`` cannot accept the
    declared :data:`OP_SURFACE` call for ``op``, else None.

    Rules: the leading positional parameter names must match the
    surface exactly (callers pass them positionally); every declared
    keyword-only name must be accepted; any parameter beyond the
    surface must have a default (so surface-shaped calls still bind).
    """
    pos_names, kw_names = OP_SURFACE[op]
    try:
        params = list(inspect.signature(impl).parameters.values())
    except (TypeError, ValueError):          # builtins / C callables
        return None
    pos = [p for p in params if p.kind in (p.POSITIONAL_ONLY,
                                           p.POSITIONAL_OR_KEYWORD)]
    kws = {p.name: p for p in params if p.kind == p.KEYWORD_ONLY}
    has_var_kw = any(p.kind == p.VAR_KEYWORD for p in params)
    got = tuple(p.name for p in pos[:len(pos_names)])
    if got != pos_names:
        return (f"positional params {got} != declared {pos_names}")
    for extra in pos[len(pos_names):]:
        if extra.default is extra.empty:
            return (f"extra positional param {extra.name!r} without a "
                    f"default breaks surface-shaped calls")
    if not has_var_kw:
        missing = [n for n in kw_names if n not in kws]
        if missing:
            return f"missing keyword params {missing}"
    for name, p in kws.items():
        if name not in kw_names and p.default is p.empty:
            return (f"extra keyword-only param {name!r} without a "
                    f"default breaks surface-shaped calls")
    return None


def validate_backend(backend: KernelBackend) -> Dict[str, str]:
    """All op-surface defects of one backend, ``op -> description``."""
    defects: Dict[str, str] = {}
    for op in OPS:
        impl = getattr(backend, op, None)
        if not callable(impl):
            defects[op] = "op not implemented (field missing/not callable)"
            continue
        bad = check_op_signature(op, impl)
        if bad:
            defects[op] = bad
    return defects


_REGISTRY: Dict[str, KernelBackend] = {}
_SCOPED: Optional[str] = None


def register_backend(backend: KernelBackend) -> KernelBackend:
    defects = validate_backend(backend)
    if defects:
        raise BackendContractError(
            f"backend {backend.name!r} violates the op surface: "
            + "; ".join(f"{op}: {d}" for op, d in sorted(defects.items())))
    _REGISTRY[backend.name] = backend
    return backend


def available_backends():
    return tuple(sorted(_REGISTRY))


def get_backend(spec: Union[None, str, KernelBackend] = None
                ) -> KernelBackend:
    """Resolve a backend: explicit arg > use_backend scope > PerfFlags."""
    if isinstance(spec, KernelBackend):
        return spec
    name = spec or _SCOPED
    if name is None:
        from repro.common.perf import get_flags
        name = get_flags().kernel_backend
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel backend {name!r}; have {available_backends()}")


@contextlib.contextmanager
def use_backend(name: str):
    """Scope the default backend (per-call args still win)."""
    global _SCOPED
    get_backend(name)          # validate eagerly
    prev = _SCOPED
    _SCOPED = name
    try:
        yield
    finally:
        _SCOPED = prev


def mesh_local() -> bool:
    """True when no device mesh is active — i.e. the Pallas (chip-local)
    kernels may replace the GSPMD-partitionable jnp paths."""
    from repro.distributed.annotate import _mesh
    return _mesh() is None


# ----------------------------------------------------------- reference ----

def _ref_router_topk(logits, k: int):
    w, i, _ = R.router_topk_ref(logits, k)
    return w, i


register_backend(KernelBackend(
    name="reference",
    attention=R.attention_ref,
    decode_attention=R.decode_attention_ref,
    paged_decode_attention=R.paged_decode_attention_ref,
    verify_attention=R.verify_attention_ref,
    paged_verify_attention=R.paged_verify_attention_ref,
    router_topk=_ref_router_topk,
    selective_scan=R.selective_scan_ref,
    mlstm_scan=R.mlstm_scan_ref,
))


# -------------------------------------------------------------- pallas ----

def _interpret() -> bool:
    """Interpret mode runs the kernels on the CPU, for tests; on a TPU
    they always compile. Any other platform has no Pallas TPU path."""
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the pallas backend runs on 'tpu' (compiled) or 'cpu' "
            f"(interpret mode), not on {platform!r}")
    return platform == "cpu"


def _pl_attention(q, k, v, *, causal=True, window=0, cap=0.0, scale=0.0,
                  q_offset=0):
    return flash_prefill(q, k, v, causal=causal, window=window, cap=cap,
                         scale=scale, q_offset=q_offset,
                         interpret=_interpret())


def _pl_decode_attention(q, k_cache, v_cache, kv_len, *, cap=0.0,
                         scale=0.0):
    return flash_decode(q, k_cache, v_cache, kv_len, cap=cap, scale=scale,
                        interpret=_interpret())


def _pl_paged_decode_attention(q, k_pages, v_pages, block_tab, kv_len, *,
                               cap=0.0, scale=0.0):
    return flash_decode_paged(q, k_pages, v_pages, block_tab, kv_len,
                              cap=cap, scale=scale,
                              interpret=_interpret())


def _pl_verify_attention(q, k_cache, v_cache, kv_len, *, cap=0.0,
                         scale=0.0):
    return flash_verify(q, k_cache, v_cache, kv_len, cap=cap, scale=scale,
                        interpret=_interpret())


def _pl_paged_verify_attention(q, k_pages, v_pages, block_tab, kv_len, *,
                               cap=0.0, scale=0.0):
    return flash_verify_paged(q, k_pages, v_pages, block_tab, kv_len,
                              cap=cap, scale=scale,
                              interpret=_interpret())


def _pl_router_topk(logits, k: int):
    return moe_router_topk(logits, k, interpret=_interpret())


def _pl_selective_scan(dt, x, B_, C_, A, h0=None):
    return ssm_scan(dt, x, B_, C_, A, h0, interpret=_interpret())


def _pl_mlstm_scan(q, k, v, i_pre, f_pre, state=None, *, scale=0.0):
    return mlstm_scan(q, k, v, i_pre, f_pre, state, scale=scale,
                      interpret=_interpret())


register_backend(KernelBackend(
    name="pallas",
    attention=_pl_attention,
    decode_attention=_pl_decode_attention,
    paged_decode_attention=_pl_paged_decode_attention,
    verify_attention=_pl_verify_attention,
    paged_verify_attention=_pl_paged_verify_attention,
    router_topk=_pl_router_topk,
    selective_scan=_pl_selective_scan,
    mlstm_scan=_pl_mlstm_scan,
))
