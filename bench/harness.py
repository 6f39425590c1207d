"""One run of one cell: set-up, the window, the metrics, the comparison.

Set-up (counted in ``setup_s``): weights from the seed, the system under
test, the traffic's prefixes, a warm-up of every shape the traffic
presents, and the traffic's lead-in, long enough that the requests in
flight have stopped growing when the window opens (an earlier line
prints them every ``FLIGHT_EVERY_S`` from the traffic's start).
The window then lasts ``--seconds``. With ``--trace 1`` the profiler
records the window (its first ``TRACE_SECONDS`` at most, and the run
ends there) and the per-layer metrics are read from it; the end-to-end
metrics come from runs with the profiler off.

After the window the peak device memory is read, the program's state is
freed, and the reference judges a sample of what the window served
(bench/correct.py).
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax

from bench import correct, devtrace, drive, spec, stats, traffic
from bench.model import Shape, make_weights, model_config, smoke
from bench.server import Server


@dataclass
class RunData:
    """What a metric reader sees (``metrics/<name>.py``: ``read(run)``)."""
    log: drive.Log
    shape: Shape
    peaks: Optional[Dict[str, float]]
    n_chips: int
    setup_s: float
    trace: Optional[devtrace.Reduced]

    def delta(self, counter: str) -> int:
        """An engine counter's change over the window, all replicas."""
        return (self.log.counters_close[counter]
                - self.log.counters_open[counter])

    def window_steps(self, decode_only: Optional[bool]):
        """Steps inside the window: only-decode ones (True), those that
        carried an admission or a prefill chunk (False), or all (None)."""
        lo, hi = self.log.open_t, self.log.close_t
        return [s for s in self.log.steps if s.t0 >= lo and s.t1 <= hi
                and (decode_only is None or s.decode_only == decode_only)]


#: the earlier line's readings of the requests in flight are this far
#: apart
FLIGHT_EVERY_S = 5.0

#: a traced run records this much of the window at most (a trace of a
#: serving window takes some 2 MB a second per busy chip)
TRACE_SECONDS = 10.0


def say(*parts) -> None:
    print(*parts, flush=True)


@dataclass
class System:
    """What set-up builds: the sizes, the weights, the system under
    test, the compile clock."""
    conf: Dict
    mix: Dict
    shape: Shape
    weights: object
    server: Server
    chips: List
    compiles: drive.CompileClock


def build(cell: spec.Cell, *, seed: int, devices,
          rehearse: bool = False) -> System:
    """Weights from the seed and the system under test, as the cell's
    configuration file says (its smoke size when rehearsing)."""
    from repro.common import perf
    from repro.serving.tokenizer import SPECIALS

    conf = smoke(cell.config) if rehearse else cell.config
    mix = smoke(cell.mix) if rehearse else cell.mix
    if "attn_chunk" in conf:
        # rehearsals scale the program's prefill chunk with the model
        perf.set_flags(dataclasses.replace(perf.get_flags(),
                                           attn_chunk=conf["attn_chunk"]))
    compiles = drive.CompileClock()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    shape = Shape.of(conf)
    chips = list(devices[:cell.chips])
    weights = make_weights(shape, seed, eos_id=SPECIALS["<eos>"])
    server = Server(model_config(conf), weights, conf["engine"],
                    conf.get("replicas", 1), chips, seed)
    return System(conf, mix, shape, weights, server, chips, compiles)


def offer(sys_: System, rate: float, seconds: float,
          seed: int) -> traffic.Traffic:
    """The run's traffic, its prefixes registered and every shape it
    presents warmed up."""
    from repro.common.perf import get_flags
    tr = traffic.generate(sys_.mix, rate, float(sys_.mix["lead_in_s"])
                          + seconds, seed)
    drive.register_prefixes(sys_.server, tr)
    drive.warm_up(sys_.server, tr, get_flags().attn_chunk)
    return tr


def run(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
        devices, peaks, t_start: float, root: str,
        rehearse: bool = False) -> Dict:
    sys_ = build(cell, seed=seed, devices=devices, rehearse=rehearse)
    conf, shape, weights, server, chips, clock = (
        sys_.conf, sys_.shape, sys_.weights, sys_.server, sys_.chips,
        sys_.compiles)
    tr = offer(sys_, float(cell.cell["rate_per_s"]), seconds, seed)
    lead_in = float(sys_.mix["lead_in_s"])
    say(f"set-up: {clock.count} programs compiled or loaded in "
        f"{clock.secs:.3f} s; traffic {traffic.describe(tr)}")

    trace_dir = os.path.join(root, ".bench_out", "trace", cell.name)
    on_open = None
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False

        def on_open():
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.open"):
                pass

    start = drive.clock()
    open_t = start + lead_in
    log = drive.serve(server, tr, start=start, open_t=open_t,
                      close_t=open_t + seconds, compiles=clock,
                      on_open=on_open)
    if trace:
        with jax.profiler.TraceAnnotation("bench.close"):
            pass
        jax.effects_barrier()
        jax.profiler.stop_trace()
    setup_s = log.open_t - t_start

    counts = stats.summary(log)
    say(f"window: {seconds} s; samples {counts}; "
        f"compiles in window {len(log.compiles_in_window)} "
        f"{log.compiles_in_window}")
    flight = [stats.in_flight(log, start + k * FLIGHT_EVERY_S)
              for k in range(int((log.close_t - start) / FLIGHT_EVERY_S)
                             + 1)]
    say(f"in flight every {FLIGHT_EVERY_S:g} s from the traffic's start "
        f"(window opens at {lead_in:g} s): {flight}")
    say(f"engine counters over the window: "
        f"{ {k: log.counters_close[k] - log.counters_open[k] for k in log.counters_close} }")

    mem = 0
    if not rehearse:
        mem = max(d.memory_stats()["peak_bytes_in_use"] for d in chips)

    reduced = None
    if trace:
        red_trace = devtrace.load(devtrace.find_xplane(trace_dir))
        reduced = devtrace.reduce(red_trace, [d.id for d in chips])
        for d, idle in sorted(reduced.idle_share.items()):
            say(f"chip {d}: busy {reduced.busy_ns[d] / 1e9:.6f} s of "
                f"{reduced.window_ns / 1e9:.6f} s, idle {100 * idle:.4f}%")

    data = RunData(log, shape, peaks, len(chips), setup_s, reduced)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = spec.reader(cell.data_dir, m["name"])(data)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the program's state goes before the reference runs
    server.close()
    del server, sys_
    gc.collect()
    verdict = correct.judge(weights, shape, log.reqs, seed,
                            conf["engine"]["cache_len"],
                            float(conf["correct"]["logit_gap_limit"]))
    say(f"compared: {verdict['requests_checked']} requests, "
        f"{verdict['tokens_checked']} served tokens, longest sequence "
        f"{verdict['longest_checked']} tokens")

    # as JAX reports it; the peak is that of the fullest chip in use
    device = {"platform": chips[0].platform, "kind": chips[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": verdict["correct"],
              "attempted": len(log.reqs),
              "failed": sum(1 for r in log.reqs if r.finish_reason
                            not in (None, "max_new_tokens", "eos")),
              "metrics": metrics, "device": device}
    if rehearse:
        # a CPU run's timings are no device's: keep them apart
        result["metrics"] = {}
        result["cpu_rehearsal"] = metrics
    if reduced is not None:
        n = len(reduced.busy_ns)
        device["busy_s"] = sum(reduced.busy_ns.values()) / n / 1e9
        device["window_s"] = reduced.window_ns / 1e9
        result["breakdown"] = devtrace.breakdown(reduced, n)
    result["compared"] = {"logit_gap": {"value": verdict["logit_gap"],
                                        "limit": verdict["limit"]}}
    print(f"logit_gap {verdict['logit_gap']} limit {verdict['limit']}",
          file=sys.stderr, flush=True)
    return result
