"""Reduce a profiler trace of the window to device busy time, idle
share, the top device operations and the longest idle gaps.

The trace is JAX's ``.xplane.pb``. Device planes are ``/device:TPU:<n>``;
the operations they ran are the events of their ``XLA Ops`` line. The
window is bounded by the benchmark's own host annotations ``bench.open``
and ``bench.close``, and an idle gap is named by the host annotation of
the benchmark that covers it (``bench.step``, ``bench.submit``, ...).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Trace:
    """Events as (start_ns, end_ns, name), per device and for the host's
    benchmark annotations."""
    devices: Dict[int, List[Tuple[int, int, str]]] = field(
        default_factory=dict)
    host: List[Tuple[int, int, str]] = field(default_factory=list)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((int(e.start_ns),
                                int(e.start_ns + e.duration_ns), e.name)
                               for e in line.events)
            tr.devices[int(m.group(1))] = sorted(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend((int(e.start_ns),
                                int(e.start_ns + e.duration_ns), e.name)
                               for e in line.events
                               if e.name.startswith("bench."))
    tr.host.sort()
    return tr


def window(tr: Trace) -> Tuple[int, int]:
    """[open, close) in the trace's clock, from the host markers."""
    opens = [s for s, _, n in tr.host if n == "bench.open"]
    closes = [s for s, _, n in tr.host if n == "bench.close"]
    if not opens or not closes:
        raise ValueError("the trace holds no bench.open/bench.close marks")
    return opens[0], closes[-1]


def clip(evs, lo: int, hi: int):
    return [(max(s, lo), min(e, hi), n) for s, e, n in evs
            if e > lo and s < hi]


def union(evs) -> List[Tuple[int, int]]:
    """Merged busy intervals of (start, end, ...) events."""
    out: List[List[int]] = []
    for s, e, *_ in sorted(evs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(evs) -> int:
    return sum(e - s for s, e in union(evs))


def gaps(evs, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Idle intervals of the device between ``lo`` and ``hi``."""
    out, t = [], lo
    for s, e in union(evs):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def host_labels(tr: Trace, spans: List[Tuple[int, int]]) -> List[str]:
    """For each (start, end) interval, the benchmark annotation that
    overlaps it most, ``untraced`` where none does. The annotations of
    the serving loop follow each other on one thread, so one sweep over
    both sorted lists finds them."""
    host = [(s, e, n) for s, e, n in tr.host if e > s]
    order = sorted(range(len(spans)), key=lambda i: spans[i])
    out = ["untraced"] * len(spans)
    j = 0
    for i in order:
        s, e = spans[i]
        while j < len(host) and host[j][1] <= s:
            j += 1
        best = 0
        k = j
        while k < len(host) and host[k][0] < e:
            ov = min(host[k][1], e) - max(host[k][0], s)
            if ov > best:
                out[i], best = host[k][2], ov
            k += 1
    return out


def op_totals(evs) -> Dict[str, int]:
    """Self time per operation name: an operation that encloses others
    on the line (a ``while`` around its body) keeps only the time none
    of them covers."""
    tot: Dict[str, int] = {}
    stack: List[List] = []            # [end, name, self time]

    def close(upto: int):
        while stack and stack[-1][0] <= upto:
            end, name, own = stack.pop()
            tot[name] = tot.get(name, 0) + own
    for s, e, n in sorted(evs, key=lambda x: (x[0], -x[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, n, e - s])
    close(float("inf"))
    return tot


def short_name(hlo: str) -> str:
    """An operation's name on the trace is its HLO text
    (``%name = <type> <opcode>(<operands>), <attributes>``); keep the
    instruction name, what it is (opcode, or a custom call's target) and
    its result's first shape."""
    m = re.match(r"(%[\w.\-]+) = ", hlo)
    if not m:
        return hlo[:80]
    rest = hlo[m.end():]
    depth, i = 0, 0
    while i < len(rest):                      # skip the result type
        c = rest[i]
        if c in "({[":
            depth += 1
        elif c in ")}]":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    op = re.match(r" ([\w\-]+)\(", rest[i:])
    target = re.search(r'custom_call_target="([^"]+)"', hlo)
    shape = re.match(r"\(?(\w+\[[\d,]*\])", rest)
    what = target.group(1) if target else (op.group(1) if op else "")
    return " ".join(x for x in (m.group(1), what,
                                shape.group(1) if shape else "") if x)


@dataclass
class Reduced:
    window_ns: int
    busy_ns: Dict[int, int]                 # per chip
    op_ns: Dict[str, int]                   # self time, summed over chips
    idle_by_host: Dict[str, Tuple[int, int, int]]   # label: (ns, n, max)

    @property
    def idle_share(self) -> Dict[int, float]:
        return {d: 1.0 - b / self.window_ns for d, b in self.busy_ns.items()}


def reduce(tr: Trace, chips: List[int]) -> Reduced:
    lo, hi = window(tr)
    busy, ops, gl = {}, {}, []
    for d in chips:
        evs = clip(tr.devices.get(d, []), lo, hi)
        busy[d] = busy_ns(evs)
        for n, ns in op_totals(evs).items():
            ops[n] = ops.get(n, 0) + ns
        gl.extend(gaps(evs, lo, hi))
    idle: Dict[str, Tuple[int, int, int]] = {}
    for (s, e), label in zip(gl, host_labels(tr, gl)):
        ns, n, mx = idle.get(label, (0, 0, 0))
        idle[label] = (ns + e - s, n + 1, max(mx, e - s))
    return Reduced(hi - lo, busy, ops, idle)


def breakdown(red: Reduced, n_chips: int, top: int = 10) -> Dict:
    """The result line's ``breakdown``: the device operations that took
    most self time, and the device's idle time by what the host was
    doing in it (each with its count and longest gap) -- seconds,
    averaged over the chips."""
    ops: Dict[str, int] = {}
    for n, ns in red.op_ns.items():
        k = short_name(n)
        ops[k] = ops.get(k, 0) + ns
    ops = sorted(ops.items(), key=lambda x: -x[1])[:top]
    idle = sorted(red.idle_by_host.items(), key=lambda x: -x[1][0])[:top]
    return {"device_ops": [[n, ns / 1e9 / n_chips] for n, ns in ops],
            "idle_gaps": [[f"{label}: {n} gaps, longest {mx / 1e6:.3f} ms",
                           ns / 1e9 / n_chips]
                          for label, (ns, n, mx) in idle]}
