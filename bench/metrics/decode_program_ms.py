"""Model step: mean device time of one run of the engine's decode
program (``jit_engine_decode`` on each chip's ``XLA Modules`` line)
over the runs that lie wholly in the traced window.

A reader is handed the trace's reduction, not its file, so this one
reads the file where ``harness.run`` writes it,
``<root>/.bench_out/trace/<cell>`` (``devtrace.find_xplane``), for the
cells ``BENCHMARK.json`` lists for this metric, and takes the one whose
window is the run's to the nanosecond. It raises when none is, and
reads None where the program's decode step has another name (a program
that predates the name).
"""
import json
import os
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "decode_program_ms"
MODULES_LINE = "XLA Modules"
DECODE_PROGRAM = "jit_engine_decode"

Events = Dict[int, List[Tuple[int, int, str]]]


def modules(path: str) -> Events:
    """Each chip's program (module) runs as (start_ns, end_ns, name)."""
    from jax.profiler import ProfileData

    from bench.devtrace import DEVICE_PLANE
    out: Events = {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            out[int(m.group(1))] = sorted(
                (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                for line in plane.lines if line.name == MODULES_LINE
                for e in line.events)
    return out


def program_ms(mods: Events, window: Tuple[int, int],
               program: str = DECODE_PROGRAM) -> Optional[float]:
    """Mean time of one run of ``program`` (``<program>`` or
    ``<program>(<id>)``) over its runs wholly inside ``window``, on
    every chip; None where it never ran there."""
    lo, hi = window
    runs = [e - s for evs in mods.values() for s, e, n in evs
            if (n == program or n.startswith(program + "("))
            and lo <= s and e <= hi]
    return 1e-6 * sum(runs) / len(runs) if runs else None


def _cells() -> List[str]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return next(m for m in bench["per_layer"]
                if m["name"] == NAME)["workloads"]


def read(run):
    if run.trace is None:
        return None
    from bench import devtrace
    for cell in _cells():
        trace_dir = os.path.join(ROOT, ".bench_out", "trace", cell)
        if not os.path.isdir(trace_dir):
            continue
        path = devtrace.find_xplane(trace_dir)
        lo, hi = devtrace.window(devtrace.load(path))
        if hi - lo == run.trace.window_ns:
            return program_ms(modules(path), (lo, hi))
    raise RuntimeError(
        f"{NAME}: no trace under {ROOT}/.bench_out/trace/<cell> for "
        f"{_cells()} has the run's window of {run.trace.window_ns} ns")
