"""Inference engine: prefill + continuous-batching decode.

A fixed pool of ``max_batch`` slots shares one batched cache pytree with
per-sequence positions (the (B,) ``pos`` vector — decode writes use
one-hot masked updates so every slot can sit at a different fill level).
Requests are prefilled on arrival (B=1) and their caches inserted into a
free slot; one ``decode_step`` advances every active slot together.

Two serving-pipeline extensions (see DESIGN.md §Pipeline concurrency):

  * **prompt-prefix caching** — ``register_prefix`` prefills a shared
    prompt prefix (e.g. the gated system prompt of one GeckOpt intent)
    once; requests tagged with that ``prefix_key`` reuse the cached
    prefill and only extend it with their suffix tokens, instead of
    recomputing the full prefix per slot;
  * **sessions** — ``open_session`` returns an ``EngineSession`` that
    multiplexes the turns of one Copilot conversation over the shared
    continuous-batching slots (each turn is one request tagged with the
    session's intent prefix).

This is the single-host engine the examples serve the planner with; the
distributed story (pjit over the production mesh) reuses exactly the same
step functions via launch/serve.py.

``kv_mode`` selects the KV-cache memory manager:

  * ``"dense"`` (default) — one (max_batch, cache_len) slab; admission
    physically copies the request's prefill (and any cached prefix)
    into its slot;
  * ``"paged"``  — a fixed budget of ``kv_blocks`` blocks of
    ``block_size`` rows (serving/kvpool.py) with per-slot block tables:
    a registered prefix's blocks are CoW-shared by every admission
    (refcount++, zero copies), admission is gated on free blocks, cold
    prefix pins are LRU-evicted under pressure and the lowest-priority
    running request is preempted-and-requeued (bit-exact swap) instead
    of dropped. Dense and paged decode are bitwise identical
    (DESIGN.md §Paged KV cache).

``spec_decode`` (a ``serving/specdec.py`` SpecConfig) turns on
draft–verify speculative decoding: every step drafts K greedy tokens
per slot with a cheap draft model and verifies them in ONE target
``verify_extend`` forward, emitting 1..K+1 tokens per slot — bitwise
identical to non-speculative decoding (T=0 always; any temperature for
seeded requests), in both kv modes (DESIGN.md §Speculative decoding).

``backend`` selects the kernel backend (kernels/backend.py) for every
jitted step — ``"pallas"`` routes prefill/extend attention through
flash_prefill, the continuous-batching decode through flash_decode (per
slot (B,) fill levels via scalar prefetch), MoE routing through the
fused top-k kernel and SSM/mLSTM state scans through their Pallas
kernels; ``"reference"`` (the default) keeps the pure-jnp paths.
DESIGN.md §Kernel backends has the selection rules and parity contract.

``prefill_budget`` makes admission-time prefill PREEMPTIBLE: instead of
running a request's whole prefill inside its admission step, the engine
splits it into ``attn_chunk``-aligned slabs (the ``prefill_extend``
machinery — bitwise identical to monolithic prefill at any seam) and
spends at most ``max(1, prefill_budget // attn_chunk)`` slabs per step
across all in-flight prefills, interleaved with decode — so one long
prompt no longer stalls every co-resident stream. ``interleave=False``
keeps the budgeted cost model but runs prefill to completion (decode
stalls while any prefill is pending) — the run-to-completion baseline
the benches compare against. ``admission`` selects the queue order:
``"fifo"`` (arrival) or ``"slack"`` (earliest SLA deadline first, and
most-slack-first preemption victims). DESIGN.md §Stall-free scheduling.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig, WINDOW_KINDS
from repro.kernels.ref import paged_gather_kv
from repro.models.model import (decode_step, init_cache, init_paged_cache,
                                prefill, prefill_extend, verify_extend)
from repro.obs import MetricsRegistry, NULL_TRACER, StatsView
from repro.serving.kvpool import BlockTable, KVBlockPool
from repro.serving.sampling import SamplerConfig, sample
from repro.serving.sched import AdmissionQueue, deadline_step, victim_key
from repro.serving.specdec import SpecConfig, SpecDecoder, check_spec_stack
from repro.serving.tokenizer import SPECIALS, TOKENIZER

KV_MODES = ("dense", "paged")

# The engine's counter surface (the legacy ``engine.stats`` keys), now a
# StatsView over the obs metrics registry. Semantics:
#   decode_steps/prefills/tokens_generated — forward counts (decode_steps
#     counts TARGET forwards, also under spec decode);
#   prefix_* — prompt-prefix cache traffic (hits, tokens saved,
#     registrations, LRU pin evictions);
#   admissions/preemptions/resumes — slot lifecycle;
#   prefill_chunks/stall_ticks/sla_expired — stall-free scheduling:
#     chunked-prefill slabs run, decode ticks skipped behind pending
#     prefills (interleave=False only), queued requests dropped past
#     their SLA deadline;
#   spec_rounds/spec_drafted/spec_accepted — speculative decoding (zero
#     when disabled): rounds = verify forwards, drafted/accepted = draft
#     token counts (accept rate = their ratio).
# The reset-audit test (tests/test_obs.py) pins this tuple against
# engine.reset() so new counters can't silently leak across runs.
ENGINE_STAT_KEYS = (
    "decode_steps", "prefills", "tokens_generated", "prefix_hits",
    "prefix_tokens_saved", "admissions", "prefix_registrations",
    "preemptions", "resumes", "prefix_evictions", "prefill_chunks",
    "stall_ticks", "sla_expired", "spec_rounds", "spec_drafted",
    "spec_accepted")


@dataclass
class Request:
    request_id: int
    prompt: List[int]
    max_new_tokens: int = 32
    sampler: SamplerConfig = field(default_factory=SamplerConfig)
    prefix_key: Optional[str] = None
    session_id: Optional[int] = None
    # SLA deadline budget in engine steps (ticks) from enqueue; None =
    # no deadline. Drives slack admission order and queued-expiry drops.
    sla_ticks: Optional[int] = None
    # filled by the engine:
    output: List[int] = field(default_factory=list)
    done: bool = False
    # "eos" | "max_new_tokens" | "cache_len" | "kv_oom" (paged: the
    # request can never fit the physical block budget) | "sla_expired"
    # (deadline passed while still queued — dropped, never admitted)
    finish_reason: Optional[str] = None
    enqueue_t: float = 0.0
    admit_t: float = 0.0
    first_token_t: float = 0.0
    finish_t: float = 0.0
    # tick-based latency stamps (engine step numbers; the cluster's tick
    # clock advances in lockstep, so these ARE cluster ticks). The wall
    # times above come from the injected clock and stay 0.0 under the
    # deterministic zero clock; the step stamps always advance.
    enqueue_step: int = 0
    admit_step: Optional[int] = None
    first_token_step: Optional[int] = None
    finish_step: Optional[int] = None
    # paged preemption: host-side copy of the KV rows generated so far
    # ({"segments": ..., "pos": n}); set while the request sits requeued
    swap: Optional[dict] = None


@dataclass
class CachedPrefix:
    ids: List[int]
    cache: dict          # B=1 prefilled cache pytree (scalar pos)
    logits: jnp.ndarray  # (1,V) logits after the prefix's last token


@dataclass
class PendingPrefill:
    """An admission whose prefill is in flight under ``prefill_budget``:
    the request owns its slot (and, paged, its block table) from
    admission, but its B=1 cache advances one budgeted chunk at a time
    across engine steps instead of monolithically inside one step. The
    first token is sampled — and the cache installed into the batched
    slot — only when the last chunk lands."""
    req: Request
    slot: int
    toks: List[int]                  # full prompt ids
    i: int                           # ids already in the cache
    logits: Optional[jnp.ndarray]    # (1,V) after toks[:i]; None pre-head
    cache: Optional[dict]            # B=1 cache pytree; None pre-head
    table: Optional[BlockTable]      # paged: blocks held from admission
    j0: int                          # paged: shared-prefix scatter skip


def _insert_slot(batched, single, slot: int):
    """Insert a B=1 cache pytree into slot `slot` of the batched cache.
    All cache leaves carry batch on axis 1 (stacked layer axis 0) except
    the (B,) pos vector."""
    def ins(b, s):
        if b.ndim >= 2 and s.shape[0] == b.shape[0] and s.ndim == b.ndim \
                and s.shape[1] == 1:
            return jax.lax.dynamic_update_slice_in_dim(b, s.astype(b.dtype),
                                                       slot, axis=1)
        return b
    out = jax.tree.map(ins, batched, single)
    return out


@jax.jit
def _paged_scatter(segments, single_segments, ids):
    """Scatter a B=1 cache's rows into pool blocks.

    ``segments``: paged pools, leaves (R, n_blocks, Hkv, bs, hd);
    ``single_segments``: a prefill/extend result, leaves
    (R, 1, Hkv, mb*bs, hd); ``ids``: (mb,) int32 destination block per
    logical block — entries >= n_blocks (the sentinel) are dropped, so
    one trace serves any row range (shared prefix blocks are skipped by
    sentinel-masking their logical indices)."""
    def ins(pages, s):
        R, nb, Hkv, bs, hd = pages.shape
        mb = s.shape[3] // bs
        upd = s[:, 0].reshape(R, Hkv, mb, bs, hd).transpose(0, 2, 1, 3, 4)
        return pages.at[:, ids].set(upd.astype(pages.dtype), mode="drop")
    return jax.tree.map(ins, segments, single_segments)


@jax.jit
def _paged_gather(segments, ids):
    """Gather one sequence's logical rows out of the pools as a B=1
    cache pytree (the swap-out payload of preemption; bit-exact, so a
    resumed request decodes as if never interrupted). ``ids``: (mb,)
    block ids, clip-padded — rows past the table are garbage and are
    never scattered back. Always full logical width: one trace for any
    fill level, paid only on the rare preemption path. The gather
    itself is kernels.ref.paged_gather_kv, vmapped over the stacked
    layer axis — one clip/sentinel rule for every paged read."""
    def g(pages):
        return jax.vmap(lambda p: paged_gather_kv(p, ids[None]))(pages)
    return jax.tree.map(g, segments)


def advance_cache_through(params, logits, cache, tokens, *, decode_fn,
                          extend_fn, can_extend: bool, pad_extend: bool,
                          cache_len: int):
    """Advance a B=1 cache through new tokens. Uses multi-token
    ``prefill_extend`` calls when the stack supports them (chunked
    prefill: whole attn_chunk slabs, then one bucket-padded call for
    the remainder so jit retraces O(log n) shapes); falls back to
    token-by-token decode otherwise. Returns (last-token logits (1,V),
    extended cache). Shared by the engine's prefix cache and the
    speculative-decode draft admissions (serving/specdec.py)."""
    from repro.common.perf import get_flags
    toks = list(tokens)
    if not toks:
        return logits, cache
    if not can_extend:
        for t in toks:
            logits, cache = decode_fn(
                params, cache, {"tokens": jnp.asarray([[t]], jnp.int32)})
        return logits, cache
    align = get_flags().attn_chunk
    i = 0
    while len(toks) - i >= align:
        chunk = jnp.asarray(toks[i:i + align], jnp.int32)[None]
        logits, cache = extend_fn(params, cache, {"tokens": chunk}, align)
        i += align
    rest = toks[i:]
    if rest:
        n = len(rest)
        # pad rows are written at [pos+n, pos+width); cap width at
        # the cache end — dynamic_update_slice would otherwise CLAMP
        # the start index and silently overwrite valid prefix rows
        room = cache_len - int(cache["pos"])
        if pad_extend and n < room:
            width = min(1 << (n - 1).bit_length(), room)
            rest = rest + [0] * (width - n)
        chunk = jnp.asarray(rest, jnp.int32)[None]
        logits, cache = extend_fn(params, cache, {"tokens": chunk}, n)
    return logits, cache


def _kv_cache_bytes(segments) -> int:
    """Total bytes of the KV leaves (k/v and cross-attention ck/cv) in a
    cache pytree's segments."""
    total = 0
    for seg in segments:
        for c in seg:
            for key in ("k", "v", "ck", "cv"):
                if isinstance(c, dict) and key in c:
                    leaf = c[key]
                    total += int(leaf.size) * leaf.dtype.itemsize
    return total


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 cache_len: int = 512, seed: int = 0,
                 backend: Optional[str] = None, kv_mode: str = "dense",
                 kv_blocks: Optional[int] = None,
                 block_size: Optional[int] = None,
                 spec_decode: Optional[SpecConfig] = None,
                 prefill_budget: Optional[int] = None,
                 interleave: bool = True,
                 admission: str = "fifo",
                 clock: Optional[Callable[[], float]] = None,
                 tracer=None, metrics: Optional[MetricsRegistry] = None):
        from repro.kernels.backend import get_backend
        self.cfg = cfg
        self.params = params
        # Request latency timestamps (enqueue/first-token/finish) come
        # from an *injected* clock; the engine itself never reads the
        # wall clock, so runs are reproducible by construction. The
        # live-serve launcher passes time.time; ticks/tests keep the
        # zero clock (timestamps all 0.0, TTFT math is tick-based).
        self._clock: Callable[[], float] = clock or (lambda: 0.0)
        # Observability (repro.obs) is injected like the clock. The
        # default NullTracer records nothing and tracing never branches
        # control flow, so tokens are bitwise identical tracer on/off;
        # a cluster passes one shared registry scoped per replica.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # wall time in traces is opt-in: only an *injected* clock is
        # ever bound — the deterministic zero clock is not a wall clock
        self.tracer.bind_clock(clock)
        # exporter track group; the cluster overwrites this with the
        # replica index so traces are keyed (replica, slot)
        self.trace_group: int = 0
        # request_id -> open "request" lifecycle span handle
        self._req_spans: Dict[int, int] = {}
        self.max_batch = max_batch
        self.cache_len = cache_len
        # resolve once so every jitted step traces one fixed backend
        self.backend = get_backend(backend).name
        self.seed = seed
        self.rng = jax.random.PRNGKey(seed)
        if kv_mode not in KV_MODES:
            raise ValueError(f"kv_mode must be one of {KV_MODES}, "
                             f"got {kv_mode!r}")
        self.kv_mode = kv_mode
        kinds = {k for unit, _ in cfg.segments for k in unit}
        if kv_mode == "paged":
            self.block_size = 16 if block_size is None else block_size
            bs = self.block_size
            if not kinds <= {"full", "dense", "moe"}:
                raise ValueError(
                    f"kv_mode='paged' needs a pure-attention stack "
                    f"(full/dense/moe), got kinds {sorted(kinds)}")
            if cache_len % bs:
                raise ValueError(f"cache_len {cache_len} must be a "
                                 f"multiple of block_size {bs}")
            # default physical budget: exactly the dense reservation
            self.kv_blocks = (kv_blocks if kv_blocks is not None
                              else max_batch * cache_len // bs)
            self.pool = KVBlockPool(self.kv_blocks, bs,
                                    metrics=self.metrics)
            self.cache = init_paged_cache(cfg, max_batch, cache_len,
                                          self.kv_blocks, bs)
            self.tables: List[Optional[BlockTable]] = [None] * max_batch
            self._prefix_tables: Dict[str, BlockTable] = {}
            self._prefix_lru: Dict[str, int] = {}
            self._lru_tick = 0
        else:
            if kv_blocks is not None or block_size is not None:
                # mirror EngineCluster's refusal of sizing kwargs that
                # would be silently dropped
                raise ValueError(
                    "kv_blocks/block_size apply only to "
                    "kv_mode='paged'")
            self.block_size = 0
            self.kv_blocks = 0
            self.pool = None
            self.cache = init_cache(cfg, max_batch, cache_len)
            self.cache["pos"] = jnp.zeros((max_batch,), jnp.int32)
        self.slots: List[Optional[Request]] = [None] * max_batch
        # admission order is a policy now (serving/sched.py): "fifo"
        # keeps the seed deque behavior, "slack" admits by SLA deadline
        self.admission = admission
        self.queue: AdmissionQueue = AdmissionQueue(admission,
                                                    metrics=self.metrics)
        self.interleave = interleave
        self.prefill_budget = prefill_budget
        # engine step counter — the tick clock every latency stamp
        # (enqueue/admit/first-token/finish steps) is expressed in
        self.step_no = 0
        # slot -> in-flight chunked prefill; _pending_rr is the
        # deficit-round-robin service order the per-step chunk
        # allowance rotates over (one chunk per turn), so a short
        # prompt drains past a long one instead of queuing behind its
        # whole prefill, and nothing starves
        self._pending: Dict[int, PendingPrefill] = {}
        self._pending_rr: deque = deque()
        self.prefixes: Dict[str, CachedPrefix] = {}
        self._next_id = 0
        self._next_session = 0
        # dict-compatible view over registry counters: same keys and
        # mapping surface as the ad-hoc dict it replaced (ENGINE_STAT_KEYS
        # documents each key), one storage for all of them
        self.stats = StatsView(self.metrics, ENGINE_STAT_KEYS)
        self._kv_bytes_total = _kv_cache_bytes(self.cache["segments"])
        self._kv_peak_blocks = 0       # paged: peak pool blocks in use
        self._kv_peak_shared = 0       # paged: peak CoW-shared blocks
        self._kv_peak_slots = 0        # dense: peak busy slots
        # wall-time waits, observed where they end: enqueue ->
        # admission, and admission -> first token (injected clock)
        self._queue_wait = self.metrics.histogram("queue_wait_s")
        self._prefill_wait = self.metrics.histogram("prefill_wait_s")

        # named functions, so their programs read jit_engine_<step> on
        # a profiler trace
        be = self.backend

        def engine_prefill(p, b):
            return prefill(p, cfg, b, cache_len=cache_len, backend=be)

        def engine_decode(p, c, b):
            return decode_step(p, cfg, c, b, backend=be)

        def engine_extend(p, c, b, n):
            return prefill_extend(p, cfg, c, b, n_valid=n, backend=be)

        def engine_verify(p, c, b):
            return verify_extend(p, cfg, c, b, backend=be)

        self._prefill = jax.jit(engine_prefill)
        self._decode = jax.jit(engine_decode)
        self._extend = jax.jit(engine_extend)
        # multi-token cache extension: no ring buffers / cross-attention;
        # bucket-padded extends additionally require a stateless
        # (pure-attention) stack — recurrent state would step through pads
        self._can_extend = (not (kinds & set(WINDOW_KINDS))
                            and "encdec" not in kinds
                            and not cfg.n_enc_layers)
        self._pad_extend = (self._can_extend
                            and kinds <= {"full", "dense", "moe"})
        if prefill_budget is not None:
            if prefill_budget < 1:
                raise ValueError(f"prefill_budget must be >= 1 token "
                                 f"per step, got {prefill_budget}")
            if not self._can_extend:
                raise ValueError(
                    "prefill_budget (chunked prefill) needs a stack "
                    "that supports multi-token prefill_extend — no "
                    "windowed/recurrent kinds and no encoder; got "
                    f"kinds {sorted(kinds)}")
        self._last_tokens = jnp.zeros((max_batch, 1), jnp.int32)

        # speculative decoding: draft K cheap tokens per slot, verify
        # them in ONE target forward (serving/specdec.py; the emitted
        # stream is bitwise identical to non-speculative decoding)
        self.spec: Optional[SpecDecoder] = None
        self._verify = None
        if spec_decode is not None:
            check_spec_stack(cfg, "target model")
            self.spec = SpecDecoder(spec_decode, max_batch=max_batch,
                                    cache_len=cache_len,
                                    backend=self.backend,
                                    metrics=self.metrics)
            self._verify = jax.jit(engine_verify)

    # ------------------------------------------------------------- API ----
    def add_request(self, prompt_text_or_ids, max_new_tokens: int = 32,
                    sampler: SamplerConfig = SamplerConfig(),
                    prefix_key: Optional[str] = None,
                    session_id: Optional[int] = None,
                    sla_ticks: Optional[int] = None) -> int:
        ids = (TOKENIZER.encode_with_specials(prompt_text_or_ids)
               if isinstance(prompt_text_or_ids, str)
               else list(prompt_text_or_ids))
        req = Request(self._next_id, ids, max_new_tokens, sampler,
                      prefix_key=prefix_key, session_id=session_id,
                      sla_ticks=sla_ticks, enqueue_t=self._clock(),
                      enqueue_step=self.step_no)
        self._next_id += 1
        if self.tracer.enabled:
            self.tracer.event("enqueue", tick=self.step_no,
                              group=self.trace_group, lane="queue",
                              request=req.request_id,
                              prompt_tokens=len(ids))
        self.queue.push(req)
        return req.request_id

    # ----------------------------------------------- load introspection ----
    # (the cluster router reads these to place requests; serving/cluster.py)
    def busy_slots(self) -> int:
        return sum(s is not None for s in self.slots)

    def free_slot_count(self) -> int:
        return self.max_batch - self.busy_slots()

    def queue_depth(self) -> int:
        return len(self.queue)

    def load(self) -> int:
        """In-flight work: occupied slots plus queued requests."""
        return self.busy_slots() + len(self.queue)

    def is_idle(self) -> bool:
        return not self.queue and all(s is None for s in self.slots)

    @property
    def spec_k(self) -> int:
        """Draft tokens per speculative round (0 = spec decode off)."""
        return self.spec.k if self.spec is not None else 0

    def reset(self, seed: Optional[int] = None):
        """Return the engine to its just-constructed state (drain and
        recycle a cluster replica between workloads). Cache storage is
        reused — stale rows are masked by the zeroed ``pos`` vector and
        overwritten at the next admission; jitted step functions are
        kept, so a reset engine serves warm."""
        if seed is not None:
            self.seed = seed
        self.rng = jax.random.PRNGKey(self.seed)
        # one sweep zeroes every registry-backed metric this engine
        # publishes (stats view, queue, pool, spec); a shared-registry
        # facade zeroes only this engine's slice. The tracer is NOT
        # cleared: a trace is a session log — spans carry their ticks,
        # and a mid-flight reset abandons the open request spans.
        self.metrics.reset()
        self._req_spans.clear()
        self.cache["pos"] = jnp.zeros((self.max_batch,), jnp.int32)
        if self.kv_mode == "paged":
            self.pool = KVBlockPool(self.kv_blocks, self.block_size,
                                    metrics=self.metrics)
            self.tables = [None] * self.max_batch
            self._prefix_tables = {}
            self._prefix_lru = {}
            self._lru_tick = 0
            self.cache["block_tab"] = jnp.full(
                (self.max_batch, self.cache_len // self.block_size),
                self.kv_blocks, jnp.int32)
        self.slots = [None] * self.max_batch
        self.queue.clear()
        self._pending.clear()
        self._pending_rr.clear()
        self.step_no = 0
        self.prefixes.clear()
        self._next_id = 0
        self._next_session = 0
        self._kv_peak_blocks = 0
        self._kv_peak_shared = 0
        self._kv_peak_slots = 0
        self._last_tokens = jnp.zeros((self.max_batch, 1), jnp.int32)
        if self.spec is not None:
            self.spec.reset()

    # -------------------------------------------------- prefix caching ----
    def register_prefix(self, key: str, prefix_text_or_ids) -> int:
        """Prefill a shared prompt prefix ONCE and cache the result.
        Returns the prefix length in tokens. Requests whose prompt starts
        with these ids (pass ``prefix_key=key``) skip the prefix prefill.
        Text prefixes are encoded as <bos> + tokens (no <eos>) so they
        concatenate with the rest of the prompt; split at whitespace.

        Prefixes longer than the attention q-chunk are prefilled on
        their chunk-aligned head and decode-extended over the tail (the
        prefill path requires Sq % attn_chunk == 0 above one chunk)."""
        from repro.common.perf import get_flags
        ids = ([SPECIALS["<bos>"]] + TOKENIZER.encode(prefix_text_or_ids)
               if isinstance(prefix_text_or_ids, str)
               else list(prefix_text_or_ids))
        assert len(ids) < self.cache_len, (len(ids), self.cache_len)
        align = get_flags().attn_chunk
        head = (ids if len(ids) <= align
                else ids[:(len(ids) // align) * align])
        prompt = jnp.asarray(head, jnp.int32)[None, :]
        logits, cache = self._prefill(self.params, {"tokens": prompt})
        self.stats["prefills"] += 1
        self.stats["prefix_registrations"] += 1
        cache = dict(cache)
        cache["pos"] = jnp.asarray(len(head), jnp.int32)
        logits, cache = self._decode_through(logits, cache,
                                             ids[len(head):])
        self.prefixes[key] = CachedPrefix(ids, cache, logits)
        if self.kv_mode == "paged":
            self._pin_prefix(key, self.prefixes[key])
        return len(ids)

    def _decode_through(self, logits, cache, tokens: List[int]
                        ) -> Tuple[jnp.ndarray, dict]:
        """Advance a B=1 cache through new tokens (see
        ``advance_cache_through``)."""
        return advance_cache_through(
            self.params, logits, cache, tokens, decode_fn=self._decode,
            extend_fn=self._extend, can_extend=self._can_extend,
            pad_extend=self._pad_extend, cache_len=self.cache_len)

    def _extend_prefix(self, pref: CachedPrefix, suffix: List[int]
                       ) -> Tuple[jnp.ndarray, dict]:
        """Advance a cached prefix cache through the suffix tokens."""
        cache = {"segments": pref.cache["segments"],
                 "pos": pref.cache["pos"]}
        return self._decode_through(pref.logits, cache, suffix)

    # ------------------------------------------------ paged KV memory ----
    # Host-side policy over serving/kvpool.py: the pool owns block ids
    # and refcounts; the engine owns what is cold (LRU prefix pins) and
    # who is lowest priority (preempt the latest-admitted request).
    def _tab_ids(self, blocks: List[int], pad: int) -> np.ndarray:
        """A full (max_blocks,) table row: real block ids then ``pad``
        (the sentinel ``kv_blocks`` for drop-masked device rows, 0 for
        clip-safe gathers)."""
        ids = np.full((self.cache_len // self.block_size,), pad, np.int32)
        ids[:len(blocks)] = blocks
        return ids

    def _pin_prefix(self, key: str, pref: CachedPrefix):
        """Write the prefix's KV rows into pool blocks ONCE and pin them
        (an LRU-evictable hold). Every admission that hits the prefix
        forks this table — refcount++, zero copies — instead of copying
        the rows into its slot. If the pool cannot hold the prefix even
        after evicting colder pins, it stays unpinned: hits still reuse
        the staged prefill, they just scatter their own copy."""
        old = self._prefix_tables.pop(key, None)
        if old is not None:
            self._prefix_lru.pop(key, None)
            self.pool.free(old)
        need = self.pool.blocks_needed(len(pref.ids))
        if need > self.pool.n_blocks or not self._reserve(need):
            return
        table = self.pool.alloc(len(pref.ids))
        ids = self._tab_ids(table.blocks, self.kv_blocks)
        self.cache["segments"] = _paged_scatter(
            self.cache["segments"], pref.cache["segments"],
            jnp.asarray(ids))
        self._prefix_tables[key] = table
        self._touch_prefix(key)
        self._note_kv_peak()

    def _touch_prefix(self, key: str):
        self._prefix_lru[key] = self._lru_tick
        self._lru_tick += 1

    def _reserve(self, need: int, keep: Optional[str] = None) -> bool:
        """True once >= ``need`` blocks are free, evicting cold prefix
        pins (LRU; never ``keep`` — the pin an admission is about to
        fork) as required. Evicts only when eviction can actually
        satisfy the request: pins are never re-established (only
        register_prefix pins), so destroying them for an unsatisfiable
        reservation would end zero-copy sharing for nothing. Never
        touches running requests — that escalation (preemption) is
        _ensure_room's call."""
        if self.pool.free_blocks() >= need:
            return True
        # blocks an eviction sweep would actually free: a pin's
        # exclusively-held blocks (shared ones stay with their forks)
        gain = sum(1 for k, t in self._prefix_tables.items()
                   if k != keep
                   for b in t.blocks if self.pool.ref[b] == 1)
        if self.pool.free_blocks() + gain < need:
            return False
        while self.pool.free_blocks() < need \
                and self._evict_cold_prefix(keep):
            pass
        return self.pool.free_blocks() >= need

    def _evict_cold_prefix(self, keep: Optional[str] = None) -> bool:
        """Evict the LRU prefix pin among those whose eviction frees at
        least one block NOW (all-shared pins are in active use — their
        blocks return via their forks anyway, so destroying the pin
        would cost future sharing and gain nothing)."""
        candidates = [k for k, t in self._prefix_tables.items()
                      if k != keep
                      and any(self.pool.ref[b] == 1 for b in t.blocks)]
        if not candidates:
            return False
        key = min(candidates, key=self._prefix_lru.get)
        self.pool.free(self._prefix_tables.pop(key))
        del self._prefix_lru[key]
        self.stats["prefix_evictions"] += 1
        if self.tracer.enabled:
            self.tracer.event("kv_evict", tick=self.step_no,
                              group=self.trace_group, lane="kv",
                              prefix=key)
        return True

    def _install(self, slot: int, req: Request, table: BlockTable,
                 single_segments, scatter_from: int):
        """Bind (request, block table) to a slot: scatter the B=1 cache
        rows of logical blocks [scatter_from, len(table)) into the
        table's blocks (blocks below ``scatter_from`` are shared prefix
        blocks — already written at pin time, never copied), then point
        the device block-table row and pos at them."""
        ids = self._tab_ids(table.blocks, self.kv_blocks)
        scat = ids.copy()
        scat[:scatter_from] = self.kv_blocks
        self.cache["segments"] = _paged_scatter(
            self.cache["segments"], single_segments, jnp.asarray(scat))
        self.cache["block_tab"] = self.cache["block_tab"].at[slot].set(
            jnp.asarray(ids))
        self.cache["pos"] = self.cache["pos"].at[slot].set(table.n_tokens)
        self.slots[slot] = req
        self.tables[slot] = table
        self._note_kv_peak()

    def _release_slot(self, slot: int):
        """Free a paged slot's blocks and sentinel its table row."""
        self.pool.free(self.tables[slot])
        self.tables[slot] = None
        self.cache["block_tab"] = self.cache["block_tab"].at[slot].set(
            self.kv_blocks)

    def _preempt(self, slot: int):
        """Swap the slot's KV rows to host memory, free its blocks and
        requeue it at the queue head. The swap payload is bit-exact, so
        the resumed request decodes the same tokens it would have —
        sampler-seeded requests are provably unperturbed (their keys
        fold in len(output)); engine-stream requests see a different key
        schedule, exactly as any co-tenancy change does."""
        req = self.slots[slot]
        table = self.tables[slot]
        gather_ids = jnp.asarray(self._tab_ids(table.blocks, 0))
        segs = jax.tree.map(np.asarray,
                            _paged_gather(self.cache["segments"],
                                          gather_ids))
        # retain only the rows the request actually holds (.copy() so
        # the slice drops the full-width base buffer); the resume path
        # pads back to the logical width, keeping one scatter trace
        rows = len(table.blocks) * self.block_size
        segs = jax.tree.map(lambda a: a[:, :, :, :rows].copy(), segs)
        req.swap = {"segments": segs, "pos": table.n_tokens}
        self.slots[slot] = None
        self.cache["pos"] = self.cache["pos"].at[slot].set(0)
        self._release_slot(slot)
        # FIFO requeues at the head (the victim resumes before new
        # arrivals); slack mode re-competes by deadline
        self.queue.push(req, front=True)
        self.stats["preemptions"] += 1
        h = self._req_spans.pop(req.request_id, None)
        if h is not None:
            self.tracer.end(h, tick=self.step_no, preempted=True,
                            tokens=len(req.output))
        if self.tracer.enabled:
            self.tracer.event("preempt", tick=self.step_no,
                              group=self.trace_group, lane="queue",
                              request=req.request_id, slot=slot)

    def _finish_now(self, req: Request, reason: str):
        req.done = True
        req.finish_reason = reason
        req.finish_t = self._clock()
        req.finish_step = self.step_no
        if not req.first_token_t:
            # finished without ever sampling (paged cache_len/kv_oom
            # refusals, sla_expired drops): leave no 0.0 sentinel for
            # TTFT math downstream
            req.first_token_t = req.finish_t
        if req.first_token_step is None:
            req.first_token_step = req.finish_step
        h = self._req_spans.pop(req.request_id, None)
        if h is not None:
            self.tracer.end(h, tick=self.step_no, reason=reason,
                            tokens=len(req.output))
        elif self.tracer.enabled:
            # never admitted (sla_expired / paged up-front refusals):
            # no lifecycle span to close — mark the drop on the queue
            # lane instead
            self.tracer.event(
                "sla_expired" if reason == "sla_expired" else "finish",
                tick=self.step_no, group=self.trace_group, lane="queue",
                request=req.request_id, reason=reason)

    def _ensure_room(self, width: int = 1) -> List[Request]:
        """Pre-decode: every active slot must own blocks for the
        ``width`` rows it is about to write (1 per decode step, K+1 per
        speculative verify — rejected rows stay in blocks the slot
        already owns, so rollback never re-enters this path). Under
        memory pressure, escalate: evict cold prefix pins (inside
        _reserve), then preempt-and-requeue the lowest-priority running
        request — never drop it. "Lowest priority" is the admission
        policy's call (sched.victim_key): FIFO preempts the
        latest-admitted request (the seed rule), slack mode the one
        with the most deadline slack. Pending chunked prefills are
        neither growers (their blocks were sized at admission) nor
        victims (their KV lives host-side until install). A lone
        request that has outgrown the whole pool finishes with
        ``kv_oom`` (nothing left to preempt)."""
        finished: List[Request] = []
        for i in range(self.max_batch):
            if self.slots[i] is None or i in self._pending:
                continue
            table = self.tables[i]
            needed_rows = min(table.n_tokens + width, self.cache_len)
            blocked = False
            while (not blocked
                   and len(table.blocks) * self.block_size < needed_rows):
                if self._reserve(1):
                    j = len(table.blocks)
                    block = self.pool.append_block(table)
                    self.cache["block_tab"] = \
                        self.cache["block_tab"].at[i, j].set(block)
                    continue
                active = [j for j in range(self.max_batch)
                          if self.slots[j] is not None
                          and j not in self._pending]
                victim = max(active, key=lambda j: victim_key(
                    self.slots[j], self.admission))
                if victim == i and len(active) == 1:
                    req = self.slots[i]
                    self._finish_now(req, "kv_oom")
                    finished.append(req)
                    self.slots[i] = None
                    self.cache["pos"] = self.cache["pos"].at[i].set(0)
                    self._release_slot(i)
                    blocked = True
                    break
                self._preempt(victim)
                if victim == i:
                    blocked = True
        self._note_kv_peak()
        return finished

    def _note_kv_peak(self):
        if self.kv_mode == "paged":
            self._kv_peak_blocks = max(self._kv_peak_blocks,
                                       self.pool.used_blocks())
            self._kv_peak_shared = max(self._kv_peak_shared,
                                       self.pool.shared_blocks())
        else:
            self._kv_peak_slots = max(self._kv_peak_slots,
                                      self.busy_slots())

    # ------------------------------------------------------- sessions ----
    def open_session(self, prefix_key: Optional[str] = None,
                     session_id: Optional[int] = None) -> "EngineSession":
        """``session_id`` defaults to an engine-local counter; a cluster
        passes its own cluster-unique ids so sessions on different
        replicas never collide (request ids are engine-local)."""
        if session_id is None:
            session_id = self._next_session
            self._next_session += 1
        return EngineSession(self, session_id, prefix_key)

    # ---------------------------------------------------- scheduling ----
    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def _request_key(self, req: Request, engine_key):
        """Sampling key for the request's next token. Engine-stream by
        default (``engine_key`` was split off ``self.rng`` either way,
        so seeded requests never perturb their neighbours' streams);
        per-request fold_in stream when the sampler carries a seed."""
        if req.sampler.seed is None:
            return engine_key
        return jax.random.fold_in(jax.random.PRNGKey(req.sampler.seed),
                                  len(req.output))

    def _prefix_hit(self, req: Request) -> Optional[CachedPrefix]:
        """The cached prefix this request can extend, if any."""
        pref = (self.prefixes.get(req.prefix_key)
                if req.prefix_key else None)
        if pref is not None and len(req.prompt) > len(pref.ids) and \
                len(req.prompt) < self.cache_len and \
                req.prompt[:len(pref.ids)] == pref.ids:
            return pref
        return None

    _UNSET = object()

    def _prefill_request(self, req: Request, pref=_UNSET):
        """Compute a request's admission logits + B=1 cache — via the
        prefix cache when it hits, full prefill otherwise. ``pref``
        takes a precomputed ``_prefix_hit`` result (paged admission
        already needs it for the block math). Returns
        (logits, cache, hit_prefix_or_None)."""
        if pref is self._UNSET:
            pref = self._prefix_hit(req)
        if pref is not None:
            logits, cache1 = self._extend_prefix(
                pref, req.prompt[len(pref.ids):])
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_saved"] += len(pref.ids)
            return logits, cache1, pref
        prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
        logits, cache1 = self._prefill(self.params, {"tokens": prompt})
        self.stats["prefills"] += 1
        return logits, dict(cache1), None

    def _note_admit(self, req: Request, slot: int):
        """Count a fresh admission and stamp it, in ticks and in wall
        time (the queue wait ends here)."""
        self.stats["admissions"] += 1
        req.admit_step = self.step_no
        req.admit_t = self._clock()
        self._queue_wait.observe(req.admit_t - req.enqueue_t)
        self._trace_admit(req, slot)

    def _trace_admit(self, req: Request, slot: int,
                     resumed: bool = False):
        """Open the request's lifecycle span on its slot lane (admit →
        finish/preempt). The paired instant on the queue lane marks the
        queue handoff; a resume opens a fresh span — one span per slot
        residency, so preempted requests show as separate segments."""
        if not self.tracer.enabled:
            return
        self.tracer.event("resume" if resumed else "admit",
                          tick=self.step_no, group=self.trace_group,
                          lane="queue", request=req.request_id,
                          slot=slot)
        self._req_spans[req.request_id] = self.tracer.begin(
            "request", tick=self.step_no, group=self.trace_group,
            lane=slot, request=req.request_id,
            prompt_tokens=len(req.prompt), resumed=resumed)

    def _first_token(self, req: Request, logits) -> bool:
        """Sample the admission token; True when it is terminal (an
        <eos> first token, or a max_new_tokens=1 budget — never decode
        past it; the request then never occupies a slot)."""
        self.rng, k = jax.random.split(self.rng)
        tok = int(sample(logits, self._request_key(req, k),
                         req.sampler)[0])
        req.output.append(tok)
        req.first_token_t = self._clock()
        req.first_token_step = self.step_no
        self._prefill_wait.observe(req.first_token_t - req.admit_t)
        if self.tracer.enabled:
            h = self._req_spans.get(req.request_id)
            lane = (self.tracer.lane_of(h)
                    if h is not None else None)
            self.tracer.event("first_token", tick=self.step_no,
                              group=self.trace_group,
                              lane="queue" if lane is None else lane,
                              request=req.request_id)
        if tok == SPECIALS["<eos>"] or \
                len(req.output) >= req.max_new_tokens:
            self._finish_now(req, "eos" if tok == SPECIALS["<eos>"]
                             else "max_new_tokens")
            return True
        return False

    def _drop_expired(self) -> List[Request]:
        """Drop queue heads whose SLA deadline has already passed while
        waiting: admitting them would burn a slot (and, paged, KV
        blocks) on a guaranteed SLA miss. Only fresh requests are
        dropped — a preempted request (non-empty output) already holds
        generated tokens and always resumes. Deterministic: only the
        queue's own order and ``step_no`` decide."""
        dropped: List[Request] = []
        while self.queue:
            req = self.queue.peek()
            if req.output or self.step_no < deadline_step(req):
                break
            self.queue.pop()
            self._finish_now(req, "sla_expired")
            self.stats["sla_expired"] += 1
            dropped.append(req)
        return dropped

    def _admit(self) -> List[Request]:
        """Prefill queued requests into free slots (or, under
        ``prefill_budget``, start their chunked prefills); returns the
        ones whose admission token was already terminal plus any
        expired-in-queue drops."""
        if self.kv_mode == "paged":
            return self._admit_paged()
        finished: List[Request] = self._drop_expired()
        free = deque(self._free_slots())
        while free and self.queue:
            slot = free[0]
            req = self.queue.pop()
            if (self.spec is not None or self.prefill_budget is not None) and \
                    len(req.prompt) >= self.cache_len:
                # plain dense truncates the prefill and emits a token
                # or two before dying with "cache_len"; that clamped
                # overflow write cannot be reproduced by one verify
                # forward — or replayed chunk-by-chunk — so spec and
                # budget modes refuse up front (the paged semantics)
                self._finish_now(req, "cache_len")
                finished.append(req)
                continue
            self._note_admit(req, slot)
            if self.prefill_budget is not None:
                free.popleft()
                self._start_pending(slot, req, self._prefix_hit(req),
                                    None, 0)
                continue
            logits, cache1, _ = self._prefill_request(req)
            if self._first_token(req, logits):
                finished.append(req)
                continue
            free.popleft()
            self.cache = _insert_slot(self.cache, cache1, slot)
            self.cache["pos"] = self.cache["pos"].at[slot].set(
                len(req.prompt))
            self.slots[slot] = req
            self._last_tokens = self._last_tokens.at[slot, 0].set(
                req.output[-1])
            if self.spec is not None:
                self.spec.admit(slot, req.prompt)
        return finished

    def _admit_paged(self) -> List[Request]:
        """Paged admission: FIFO like dense, but gated on free blocks —
        a queue head that does not fit (after LRU-evicting cold prefix
        pins) WAITS for running requests to free memory instead of being
        admitted or dropped. Requests that can never fit the pool finish
        immediately with ``kv_oom``; preempted requests at the head are
        restored from their swap payload without recomputation."""
        finished: List[Request] = self._drop_expired()
        free = deque(self._free_slots())
        while free and self.queue:
            slot = free[0]
            req = self.queue.peek()
            if req.swap is not None:                       # resume
                total = req.swap["pos"]
                # +1: room for the decode write this same step — without
                # it a resumed request preempts itself right back out
                need = self.pool.blocks_needed(total + 1)
                if need > self.pool.n_blocks:
                    self.queue.pop()
                    self._finish_now(req, "kv_oom")
                    finished.append(req)
                    continue
                if not self._reserve(need):
                    break                                  # wait
                self.queue.pop()
                # hold the decode-write headroom block NOW — a reserve
                # that is only re-checked later can be consumed by the
                # next admission in this same loop
                table = self.pool.alloc(total + 1)
                table.n_tokens = total
                # pad the sliced swap rows back to the logical width so
                # _paged_scatter keeps one trace for any fill level
                pad = self.cache_len
                segs = jax.tree.map(
                    lambda a: np.pad(a, ((0, 0), (0, 0), (0, 0),
                                         (0, pad - a.shape[3]),
                                         (0, 0))),
                    req.swap["segments"])
                self._install(slot, req, table, segs, scatter_from=0)
                self._last_tokens = self._last_tokens.at[slot, 0].set(
                    req.output[-1])
                req.swap = None
                self.stats["resumes"] += 1
                self._trace_admit(req, slot, resumed=True)
                if self.spec is not None:
                    # the swap restored the target's KV, but the draft
                    # cache was dropped at preemption — rebuild it over
                    # the same context (prompt + output minus the
                    # carried last token)
                    self.spec.admit(slot,
                                    req.prompt + req.output[:-1])
                free.popleft()
                continue
            total = len(req.prompt)
            if total >= self.cache_len:
                # no room in the logical view for even one decode write;
                # dense truncates the prefill and emits a token or two
                # before dying with "cache_len" — paged refuses up front
                # instead of letting the block math run off the table
                self.queue.pop()
                self._finish_now(req, "cache_len")
                finished.append(req)
                continue
            # zero-copy sharing needs the prefix PINNED (its blocks in
            # the pool); a hit on an evicted pin still reuses the staged
            # prefill but scatters a private copy (j0 = 0)
            pref = self._prefix_hit(req)
            ptab = (self._prefix_tables.get(req.prefix_key)
                    if pref is not None else None)
            j0 = (len(pref.ids) // self.block_size
                  if ptab is not None else 0)
            if ptab is not None:
                # LRU-touch at the hit decision, not after install — a
                # terminal-first-token admission must still keep a hot
                # pin warm
                self._touch_prefix(req.prefix_key)
            # +1 as above: prompt blocks plus the imminent decode write
            need = self.pool.blocks_needed(total + 1) - j0
            if need > self.pool.n_blocks:
                self.queue.pop()
                self._finish_now(req, "kv_oom")
                finished.append(req)
                continue
            if not self._reserve(need, keep=(req.prefix_key
                                             if ptab is not None
                                             else None)):
                if self.busy_slots() > 0:
                    break      # wait: running requests will free blocks
                # nothing running will ever free blocks; last resort,
                # retry as a private (unshared) copy — this may evict
                # the very pin we would have forked, the only remaining
                # path to progress
                if ptab is not None:
                    ptab, j0 = None, 0
                    need = self.pool.blocks_needed(total + 1)
                if not self._reserve(need):
                    # the head can never fit — fail it, don't deadlock
                    self.queue.pop()
                    self._finish_now(req, "kv_oom")
                    finished.append(req)
                    continue
            self.queue.pop()
            self._note_admit(req, slot)
            if self.prefill_budget is not None:
                # chunked admission: take the blocks NOW (same math as
                # the monolithic path below) so co-resident decodes
                # cannot starve the in-flight prefill of its own rows,
                # then advance chunk-by-chunk across steps
                if ptab is not None:
                    table = self.pool.fork(ptab, total)
                    self.pool.cow_from(table, j0)
                    self.pool.grow(table, total + 1)
                    if self.tracer.enabled:
                        self.tracer.event("cow_fork", tick=self.step_no,
                                          group=self.trace_group,
                                          lane="kv",
                                          request=req.request_id,
                                          shared_blocks=j0)
                else:
                    table = self.pool.alloc(total + 1)
                table.n_tokens = total
                free.popleft()
                self._start_pending(slot, req, pref, table, j0)
                self._note_kv_peak()
                continue
            logits, cache1, _ = self._prefill_request(req, pref)
            if self._first_token(req, logits):
                finished.append(req)
                continue
            # the +1 headroom block is allocated (held), not just
            # reserved — see the resume path above
            if ptab is not None:
                # CoW fork: share every fully-covered prefix block
                # (refcount++), own a fresh copy of the partial tail
                # block and the suffix blocks
                table = self.pool.fork(ptab, total)
                self.pool.cow_from(table, j0)
                self.pool.grow(table, total + 1)
                if self.tracer.enabled:
                    self.tracer.event("cow_fork", tick=self.step_no,
                                      group=self.trace_group,
                                      lane="kv",
                                      request=req.request_id,
                                      shared_blocks=j0)
            else:
                table = self.pool.alloc(total + 1)
            table.n_tokens = total
            self._install(slot, req, table, cache1["segments"],
                          scatter_from=j0)
            self._last_tokens = self._last_tokens.at[slot, 0].set(
                req.output[-1])
            if self.spec is not None:
                self.spec.admit(slot, req.prompt)
            free.popleft()
        return finished

    def _start_pending(self, slot: int, req: Request,
                       pref: Optional[CachedPrefix],
                       table: Optional[BlockTable], j0: int):
        """Open a chunked prefill: the request takes its slot (and, in
        paged mode, its pre-allocated block table) immediately, but its
        B=1 cache is built across subsequent steps by
        ``_advance_pendings``. A prefix hit seeds the cache from the
        registered prefill exactly like the monolithic path."""
        i, logits, cache = 0, None, None
        if pref is not None:
            i = len(pref.ids)
            logits = pref.logits
            cache = {"segments": pref.cache["segments"],
                     "pos": pref.cache["pos"]}
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_saved"] += i
        else:
            self.stats["prefills"] += 1
        self.slots[slot] = req
        self._pending[slot] = PendingPrefill(
            req=req, slot=slot, toks=list(req.prompt), i=i,
            logits=logits, cache=cache, table=table, j0=j0)
        self._pending_rr.append(slot)

    def _advance_pending(self, p: PendingPrefill, chunks: int) -> int:
        """Spend up to ``chunks`` whole attn_chunk slabs advancing one
        pending prefill; returns the slabs consumed. Every call is the
        same ``prefill``/``prefill_extend`` sequence
        ``advance_cache_through`` would issue, just spread across
        steps — the chunk-seam parity of prefill_extend (DESIGN.md
        §Prefix caching) makes the result bitwise identical to a
        monolithic prefill no matter where the budget cuts."""
        from repro.common.perf import get_flags
        align = get_flags().attn_chunk
        spent = 0
        while spent < chunks and p.i < len(p.toks):
            rem = len(p.toks) - p.i
            n = min(rem, align)
            kind = ("head" if p.cache is None
                    else "extend" if n == align else "pad_extend")
            with self.tracer.span("engine.prefill", tick=self.step_no,
                                  group=self.trace_group, lane="engine",
                                  request=p.req.request_id, tokens=n,
                                  kind=kind):
                if kind == "head":
                    # one B=1 prefill over the first chunk (or the
                    # whole short prompt)
                    head = jnp.asarray(p.toks[:n], jnp.int32)[None]
                    logits, cache = self._prefill(self.params,
                                                  {"tokens": head})
                    cache = dict(cache)
                    cache["pos"] = jnp.asarray(n, jnp.int32)
                    p.logits, p.cache = logits, cache
                    p.i = n
                elif kind == "extend":
                    chunk = jnp.asarray(p.toks[p.i:p.i + align],
                                        jnp.int32)[None]
                    p.logits, p.cache = self._extend(
                        self.params, p.cache, {"tokens": chunk}, align)
                    p.i += align
                else:
                    # bucket-padded remainder — advance_cache_through's
                    # tail rule (cap the pad width at the cache end)
                    rest = p.toks[p.i:]
                    room = self.cache_len - int(p.cache["pos"])
                    if self._pad_extend and rem < room:
                        width = min(1 << (rem - 1).bit_length(), room)
                        rest = rest + [0] * (width - rem)
                    chunk = jnp.asarray(rest, jnp.int32)[None]
                    p.logits, p.cache = self._extend(
                        self.params, p.cache, {"tokens": chunk}, rem)
                    p.i = len(p.toks)
            spent += 1
        self.stats["prefill_chunks"] += spent
        if spent and self.tracer.enabled:
            self.tracer.event("prefill_chunk", tick=self.step_no,
                              group=self.trace_group, lane=p.slot,
                              request=p.req.request_id, chunks=spent,
                              done_tokens=p.i)
        return spent

    def _complete_pending(self, slot: int) -> Optional[Request]:
        """Last chunk landed: sample the admission token and install
        the finished B=1 cache into the batched slot (dense copy or
        paged scatter — identical to the monolithic admission tail).
        Returns the request when its first token was already terminal
        (the slot frees without ever decoding)."""
        p = self._pending.pop(slot)
        req = p.req
        if self._first_token(req, p.logits):
            self.slots[slot] = None
            if self.kv_mode == "paged":
                self.pool.free(p.table)
            return req
        if self.kv_mode == "paged":
            self._install(slot, req, p.table, p.cache["segments"],
                          scatter_from=p.j0)
        else:
            self.cache = _insert_slot(self.cache, p.cache, slot)
            self.cache["pos"] = self.cache["pos"].at[slot].set(
                len(req.prompt))
        self._last_tokens = self._last_tokens.at[slot, 0].set(
            req.output[-1])
        if self.spec is not None:
            self.spec.admit(slot, req.prompt)
        return None

    def _advance_pendings(self) -> List[Request]:
        """Spend this step's prefill budget — ``max(1, prefill_budget
        // attn_chunk)`` slabs (a budget smaller than one chunk falls
        back to whole-chunk granularity) — over pending prefills in
        deficit round-robin: one chunk per turn, an unfinished prefill
        rotates to the back. A short prompt therefore drains past a
        long one in a few turns instead of queuing behind its whole
        prefill (head-of-line order would stall every wavemate's first
        token for the longest prompt), and the rotation is
        starvation-free: with k pendings every prefill advances at
        least every k turns. A prefill that finishes completes
        immediately: its admission token is emitted this step and the
        slot joins this same step's decode, matching the monolithic
        path's timing relative to prefill completion."""
        from repro.common.perf import get_flags
        allowance = max(1, self.prefill_budget // get_flags().attn_chunk)
        finished: List[Request] = []
        while allowance > 0 and self._pending_rr:
            slot = self._pending_rr[0]
            p = self._pending[slot]
            allowance -= self._advance_pending(p, 1)
            if p.i >= len(p.toks):
                self._pending_rr.popleft()
                with self.tracer.span("engine.prefill", tick=self.step_no,
                                      group=self.trace_group,
                                      lane="engine",
                                      request=p.req.request_id,
                                      tokens=0, kind="complete"):
                    done = self._complete_pending(slot)
                if done is not None:
                    finished.append(done)
            else:
                self._pending_rr.rotate(-1)
        return finished

    def step(self) -> List[Request]:
        """One engine iteration (one tick of ``step_no``): admit from
        the queue, advance pending chunked prefills by the per-step
        budget, then decode one token for every active slot — or, with
        spec decode on, draft K cheap tokens per slot and verify them
        in one target forward, emitting 1..K+1 tokens per slot
        (_spec_step). With ``interleave=False`` decode (and spec) is
        skipped while any prefill is pending — the run-to-completion
        baseline. Returns newly finished requests (including any that
        terminated on their admission token and expired-in-queue
        drops). Paged mode additionally grows block tables before the
        decode/verify writes and may preempt-and-requeue under memory
        pressure (_ensure_room)."""
        tr = self.tracer
        with tr.span("engine.step", tick=self.step_no,
                     group=self.trace_group, lane="engine",
                     step_no=self.step_no, queued=len(self.queue)) as sp:
            if tr.enabled:
                admitted = self.stats["admissions"]
                chunks = self.stats["prefill_chunks"]
            finished, decoded = self._step_once()
            if tr.enabled:
                sp.set(admitted=self.stats["admissions"] - admitted,
                       chunks=self.stats["prefill_chunks"] - chunks,
                       decoded=decoded)
        self.step_no += 1
        return finished

    def _step_once(self) -> Tuple[List[Request], int]:
        """The body of ``step``; also returns how many slots decoded."""
        with self.tracer.span("engine.admit", tick=self.step_no,
                              group=self.trace_group, lane="engine"):
            finished = self._admit()
        self._note_kv_peak()
        if self._pending:
            finished.extend(self._advance_pendings())
        stalled = not self.interleave and bool(self._pending)
        if self.kv_mode == "paged" and not stalled:
            finished.extend(self._ensure_room(
                1 if self.spec is None else self.spec.k + 1))
        active = [i for i, s in enumerate(self.slots)
                  if s is not None and i not in self._pending]
        if not active:
            return finished, 0
        if stalled:
            self.stats["stall_ticks"] += 1
            if self.tracer.enabled:
                self.tracer.event("stall", tick=self.step_no,
                                  group=self.trace_group, lane="engine",
                                  pending=len(self._pending))
            return finished, 0
        if self.spec is not None:
            finished.extend(self._spec_step(active))
            return finished, len(active)
        # the dispatch only: the first token sync in engine.sample waits
        # for the program (a wait here would stop the host dispatching
        # the first slot's sampling while the program runs)
        with self.tracer.span("engine.decode", tick=self.step_no,
                              group=self.trace_group, lane="engine",
                              slots=len(active)):
            logits, self.cache = self._decode(
                self.params, self.cache, {"tokens": self._last_tokens})
        self.stats["decode_steps"] += 1
        if self.tracer.enabled:
            self.tracer.event("decode", tick=self.step_no,
                              group=self.trace_group, lane="engine",
                              active=len(active))
        if self.kv_mode == "paged":
            for i in active:          # one KV row written per sequence
                self.tables[i].n_tokens += 1
        # per-slot sampling: each slot draws its own engine-stream key,
        # unless the request carries a per-request seed (_request_key)
        with self.tracer.span("engine.sample", tick=self.step_no,
                              group=self.trace_group, lane="engine",
                              slots=len(active)):
            for i in active:
                req = self.slots[i]
                self.rng, ki = jax.random.split(self.rng)
                tok = int(sample(logits[i:i + 1],
                                 self._request_key(req, ki),
                                 req.sampler)[0])
                req.output.append(tok)
                self.stats["tokens_generated"] += 1
                self._last_tokens = self._last_tokens.at[i, 0].set(tok)
                hit_cap = len(req.output) >= req.max_new_tokens
                hit_len = (int(self.cache["pos"][i]) + 1
                           >= self.cache_len - 1)
                if tok == SPECIALS["<eos>"] or hit_cap or hit_len:
                    self._finish_now(
                        req, "eos" if tok == SPECIALS["<eos>"]
                        else "max_new_tokens" if hit_cap
                        else "cache_len")
                    finished.append(req)
                    self.slots[i] = None
                    self.cache["pos"] = self.cache["pos"].at[i].set(0)
                    if self.kv_mode == "paged":
                        self._release_slot(i)
        return finished, len(active)

    def _spec_step(self, active: List[int]) -> List[Request]:
        """One speculative round: K greedy draft steps, one target
        verify forward over W = K+1 positions per slot, then per-slot
        sample-and-match acceptance (serving/specdec.py has the
        protocol and the bitwise-parity argument).

        Every emitted token is sampled from the target's verify logits
        with the exact key schedule non-speculative decoding would use
        (per-request fold_in streams; the engine stream still splits
        once per sampled token), and the finish checks replicate
        step()'s eos/max_new_tokens/cache_len decisions token by token
        — so outputs AND finish reasons match the non-speculative
        engine bitwise."""
        k = self.spec.k
        pos0 = np.asarray(self.cache["pos"])
        drafts = self.spec.draft(self._last_tokens)           # (B, k)
        toks = jnp.concatenate(
            [self._last_tokens, jnp.asarray(drafts, jnp.int32)], axis=1)
        vlogits, self.cache = self._verify(self.params, self.cache,
                                           {"tokens": toks})
        self.stats["decode_steps"] += 1
        self.stats["spec_rounds"] += 1
        new_pos = pos0.copy()
        finished: List[Request] = []
        full_accept = False
        round_accepted = 0
        for i in active:
            req = self.slots[i]
            emitted = accepted = 0
            reason = None
            for j in range(k + 1):
                self.rng, kj = jax.random.split(self.rng)
                key = self._request_key(req, kj)
                tok = int(sample(vlogits[i, j][None], key,
                                 req.sampler)[0])
                req.output.append(tok)
                emitted += 1
                self.stats["tokens_generated"] += 1
                matched = j < k and tok == int(drafts[i, j])
                if matched:
                    accepted += 1
                hit_cap = len(req.output) >= req.max_new_tokens
                hit_len = int(pos0[i]) + j + 2 >= self.cache_len - 1
                reason = ("eos" if tok == SPECIALS["<eos>"]
                          else "max_new_tokens" if hit_cap
                          else "cache_len" if hit_len else None)
                if reason is not None or not matched:
                    break
            self.stats["spec_drafted"] += k
            self.stats["spec_accepted"] += accepted
            round_accepted += accepted
            full_accept = full_accept or accepted == k
            new_pos[i] = int(pos0[i]) + emitted
            self._last_tokens = self._last_tokens.at[i, 0].set(
                req.output[-1])
            if self.kv_mode == "paged":
                # rollback IS this truncation: rejected rows sit in
                # blocks the table already holds and are overwritten
                # before kv_len ever reaches them
                self.tables[i].n_tokens = int(pos0[i]) + emitted
            if reason is not None:
                self._finish_now(req, reason)
                finished.append(req)
                self.slots[i] = None
                new_pos[i] = 0
                if self.kv_mode == "paged":
                    self._release_slot(i)
        if self.tracer.enabled:
            self.tracer.event("spec_round", tick=self.step_no,
                              group=self.trace_group, lane="engine",
                              active=len(active),
                              drafted=k * len(active),
                              accepted=round_accepted)
        self.cache["pos"] = jnp.asarray(new_pos, jnp.int32)
        if full_accept:
            self.spec.catch_up()
        self.spec.set_pos(new_pos)
        return finished

    def run_until_done(self, max_iters: int = 10_000) -> List[Request]:
        done: List[Request] = []
        it = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and it < max_iters:
            done.extend(self.step())
            it += 1
        return done

    def throughput_stats(self) -> Dict[str, float]:
        st = {**self.stats, **self.kv_memory_stats()}
        # tokens per TARGET forward — the number speculative decoding
        # moves (> busy slots when drafts are accepted); accept rate =
        # accepted / drafted over every speculative round
        st["tokens_per_step"] = round(
            st["tokens_generated"] / max(st["decode_steps"], 1), 4)
        st["spec_accept_rate"] = round(
            st["spec_accepted"] / max(st["spec_drafted"], 1), 4)
        st["spec_k"] = self.spec_k
        return st

    def kv_memory_stats(self) -> Dict:
        """KV-memory accounting, apples-to-apples across modes:
        ``kv_bytes_allocated`` is the physical reservation (dense: the
        full (max_batch, cache_len) slab; paged: the block pool),
        ``kv_bytes_in_use``/``kv_bytes_peak`` what live requests
        actually hold (dense reserves a whole slot per request), and
        ``kv_shared_frac`` the fraction of in-use blocks CoW-shared
        between holders (dense never shares)."""
        if self.kv_mode == "paged":
            ps = self.pool.stats()
            bpb = self._kv_bytes_total // max(self.kv_blocks, 1)
            used = ps["kv_blocks_used"]
            return {**ps, "kv_mode": "paged",
                    "kv_bytes_allocated": self._kv_bytes_total,
                    "kv_bytes_in_use": used * bpb,
                    "kv_bytes_peak": self._kv_peak_blocks * bpb,
                    "kv_blocks_used_peak": self._kv_peak_blocks,
                    "kv_blocks_shared_peak": self._kv_peak_shared,
                    # peak-based: after a run drains, request tables are
                    # freed and the instantaneous shared count is ~0 —
                    # the peaks are what the run actually exercised
                    "kv_shared_frac": round(
                        self._kv_peak_shared
                        / max(self._kv_peak_blocks, 1), 4)}
        per_slot = self._kv_bytes_total // max(self.max_batch, 1)
        return {"kv_mode": "dense",
                "kv_bytes_allocated": self._kv_bytes_total,
                "kv_bytes_in_use": self.busy_slots() * per_slot,
                "kv_bytes_peak": self._kv_peak_slots * per_slot,
                "kv_blocks_total": 0, "kv_blocks_used": 0,
                "kv_blocks_free": 0, "kv_blocks_shared": 0,
                "kv_blocks_owned": 0, "kv_blocks_used_peak": 0,
                "kv_blocks_shared_peak": 0, "kv_shared_frac": 0.0}


@dataclass
class EngineSession:
    """One Copilot conversation multiplexed over the engine's slots.

    Each planner/gate turn becomes one engine request tagged with the
    session's ``prefix_key`` (its gated intent), so every turn of every
    session sharing an intent reuses the same cached system-prompt
    prefill. Turns from many sessions interleave freely in the slot pool
    — the engine does not reserve a slot per session.
    """
    engine: InferenceEngine
    session_id: int
    prefix_key: Optional[str] = None
    pending: List[int] = field(default_factory=list)
    turns: List[Request] = field(default_factory=list)

    def submit_turn(self, text: str, max_new_tokens: int = 16,
                    sampler: SamplerConfig = SamplerConfig()) -> int:
        rid = self.engine.add_request(text, max_new_tokens, sampler,
                                      prefix_key=self.prefix_key,
                                      session_id=self.session_id)
        self.pending.append(rid)
        return rid

    def collect(self, finished: List[Request]) -> List[Request]:
        """Claim this session's turns from an engine ``step`` result.
        Matches on (session_id, request_id): a cluster merges finished
        lists from many replicas, and request ids are only engine-local."""
        mine = [r for r in finished if r.session_id == self.session_id
                and r.request_id in self.pending]
        for r in mine:
            self.pending.remove(r.request_id)
            self.turns.append(r)
        return mine

    @property
    def idle(self) -> bool:
        return not self.pending
