"""Drive the system under test: warm-up, then open-loop traffic in wall
time, with every output token stamped when ``step()`` returns.

Sessions arrive on their schedule whether or not the system keeps up.
A turn is due at its session's arrival (turn 1) or at the previous
turn's finish plus its think time; every latency is timed from that due
time, so a request that waits to be submitted counts the wait.
"""
from __future__ import annotations

import heapq
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax

from bench.traffic import Traffic

clock = time.perf_counter


class CompileClock:
    """Counts and times JAX's compile events (a persistent-cache load
    counts too: it is the same event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.secs = 0.0
        self.names: List[str] = []

    def __call__(self, event: str, duration: float, **kw):
        if event == self.EVENT:
            self.count += 1
            self.secs += duration
            self.names.append(str(kw.get("fun_name", "?")))


@dataclass
class Req:
    """The benchmark's record of one request."""
    index: int
    session: int
    turn: int
    due: float
    prompt: List[int]
    max_new: int
    prefix_key: Optional[str]
    cached_prefix: int = 0          # prefix tokens the system holds
    replica: int = -1
    submit_t: Optional[float] = None
    finish_t: Optional[float] = None
    finish_reason: Optional[str] = None
    stamps: List[float] = field(default_factory=list)
    output: List[int] = field(default_factory=list)

    @property
    def first_t(self) -> Optional[float]:
        return self.stamps[0] if self.stamps else None


@dataclass
class Step:
    """One ``step()`` call: its wall interval, whether it only decoded
    (no admission, no prefill chunk), and the keys each slot that
    decoded in it attended."""
    t0: float
    t1: float
    decode_only: bool
    kv_lens: List[int]


@dataclass
class Log:
    reqs: List[Req]
    steps: List[Step]
    unsubmitted: List[Req]          # due before the close, never handed in
    open_t: float
    close_t: float
    counters_open: Dict[str, int]
    counters_close: Dict[str, int]
    compiles_in_window: List[str]       # programs compiled in the window


def prefix_key(traffic: Traffic, intent: Optional[int]) -> Optional[str]:
    return None if intent is None else traffic.prefix_keys[intent]


def register_prefixes(server, traffic: Traffic) -> None:
    for key, ids in zip(traffic.prefix_keys, traffic.prefixes):
        server.register_prefix(key, ids)


def _run_until_idle(server, limit: int = 100_000) -> None:
    n = 0
    while not server.idle():
        server.step()
        n += 1
        if n > limit:
            raise RuntimeError("warm-up did not drain")


def warm_up(server, traffic: Traffic, attn_chunk: int) -> None:
    """Run every program shape the traffic will present, on every
    replica it will reach, through the same entry points: each
    bucket-padded extend width (powers of two up to ``attn_chunk``), the
    head prefill of unshared prompts, decode, and admissions that wait
    on each other. (The decode program is one shape for any number of
    busy slots, and the per-slot host operations take the slot as an
    operand, so no slot count needs its own warm-up.)"""
    widths = []
    w = 1
    while w <= min(attn_chunk, traffic.max_context):
        widths.append(w)
        w *= 2
    # one intent per replica that is home to any (None: no prefixes)
    homes: Dict[int, Optional[int]] = {}
    for k in range(len(traffic.prefixes)):
        homes.setdefault(server.prefix_home(traffic.prefix_keys[k]), k)
    if not traffic.prefixes:
        homes = {r: None for r in range(len(server.engines))}
    idx = -1
    for replica, intent in sorted(homes.items()):
        key = prefix_key(traffic, intent)
        head = (traffic.prefixes[intent] if intent is not None
                else traffic.ids(replica, 0, attn_chunk, stream=2))
        # one request per extend width, one at a time: right after the
        # cached head, and after one whole chunk more (the position then
        # comes from the previous extend, which is placed differently)
        for lead in (0, attn_chunk):
            for w in widths:
                n = lead + w
                if len(head) + n + 2 >= server.cache_len:
                    continue
                server.submit(idx, head + traffic.ids(replica, n, n,
                                                      stream=3), 2, key)
                idx -= 1
                _run_until_idle(server)
        # a few at once: admissions queue behind each other's chunks
        for j in range(min(4, server.max_batch)):
            server.submit(idx, head + traffic.ids(replica, j, 16, stream=4),
                          3, key)
            idx -= 1
        _run_until_idle(server)
    jax.effects_barrier()


def serve(server, traffic: Traffic, *, start: float, open_t: float,
          close_t: float, compiles: CompileClock,
          on_open: Optional[Callable[[], None]] = None) -> Log:
    """Offer the traffic from ``start`` until ``close_t``; the window
    is [open_t, close_t). ``on_open`` runs once as the window opens
    (the traced run starts the profiler there)."""
    heap = [(start + s.arrival_s, s.index, 0) for s in traffic.sessions]
    heapq.heapify(heap)
    by_session = {s.index: s for s in traffic.sessions}
    reqs: List[Req] = []
    last_prompt: Dict[int, List[int]] = {}
    steps: List[Step] = []
    counters_open = None
    compiles_open = None
    ann = jax.profiler.TraceAnnotation

    def submit(due: float, sid: int, turn: int) -> None:
        s = by_session[sid]
        t = s.turns[turn]
        with ann("bench.generate"):
            new = traffic.ids(sid, turn, t.extra_tokens)
            if turn == 0:
                head = (traffic.prefixes[s.intent]
                        if s.intent is not None else [])
                prompt = head + new
            else:
                prev = reqs[last_prompt[sid]]
                prompt = prev.prompt + prev.output + new
        key = prefix_key(traffic, s.intent)
        r = Req(len(reqs), sid, turn, due, prompt, t.output_tokens, key)
        r.cached_prefix = len(traffic.prefixes[s.intent]) \
            if s.intent is not None else 0
        with ann("bench.submit"):
            r.replica = server.submit(r.index, prompt, r.max_new, key)
        r.submit_t = clock()
        reqs.append(r)
        last_prompt[sid] = r.index

    def next_turn(r: Req) -> None:
        s = by_session[r.session]
        nxt = r.turn + 1
        if nxt >= len(s.turns):
            return
        size = (len(r.prompt) + len(r.output) + s.turns[nxt].extra_tokens)
        if size + traffic.max_output > traffic.max_context:
            return                      # the session's context is full
        heapq.heappush(heap, (r.finish_t + s.turns[nxt].think_s,
                              r.session, nxt))

    while True:
        now = clock()
        if counters_open is None and now >= open_t:
            if on_open is not None:
                on_open()
            counters_open = server.counters()
            compiles_open = compiles.count
        if now >= close_t:
            break
        while heap and heap[0][0] <= now:
            due, sid, turn = heapq.heappop(heap)
            submit(due, sid, turn)
        if server.idle():
            wake = min(heap[0][0] if heap else close_t, close_t,
                       open_t if counters_open is None else close_t)
            with ann("bench.wait"):
                time.sleep(max(0.0, wake - clock()))
            continue
        before = server.counters()
        t0 = clock()
        with ann("bench.step"):
            finished = server.step()
        t1 = clock()
        after = server.counters()
        kv: List[int] = []
        for req in list(server.live()) + list(finished):
            rec = reqs[req.session_id]
            n0, n1 = len(rec.stamps), len(req.output)
            if n1 > n0:
                # one decode per step: the keys it attended are the
                # prompt and the tokens served before it
                if n0 >= 1:
                    kv.append(len(rec.prompt) + n0)
                elif n1 >= 2:
                    kv.append(len(rec.prompt) + 1)
                rec.stamps.extend([t1] * (n1 - n0))
        for req in finished:
            rec = reqs[req.session_id]
            rec.output = list(req.output)
            rec.finish_t = t1
            rec.finish_reason = req.finish_reason
            next_turn(rec)
        decode_only = (after["decode_steps"] > before["decode_steps"]
                       and after["admissions"] == before["admissions"]
                       and after["prefill_chunks"]
                       == before["prefill_chunks"])
        steps.append(Step(t0, t1, decode_only, kv))
    unsubmitted = []
    for due, sid, turn in heap:
        if due < close_t:
            unsubmitted.append(Req(-1, sid, turn, due, [], 0, None))
    return Log(reqs, steps, unsubmitted, open_t, close_t, counters_open,
               server.counters(), compiles.names[compiles_open:])
