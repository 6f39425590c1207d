"""Observability layer: tracer semantics, metrics registry, Perfetto
export, and the determinism contracts they must uphold.

Four claims under test (DESIGN.md §Observability):

  * well-formedness — spans pair B/E per track, seq is strictly
    increasing, a drained run leaves no open spans;
  * byte identity — same seed ⇒ byte-identical serialized trace
    (single engine AND a 2-replica cluster, through the Chrome
    exporter); a wall-stamped trace exports in wall time;
  * zero perturbation — tokens are bitwise identical with the tracer
    on vs the NullTracer default (tracing never branches control flow);
  * reset audit — ``engine.reset()``/``cluster.reset()`` zero the FULL
    counter surface (ENGINE_STAT_KEYS is pinned here so a new counter
    cannot silently leak across runs).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.agent import Agent
from repro.core.gate import IntentGate, ScriptedIntentClassifier
from repro.core.intents import build_intent_map
from repro.core.planner import PlannerConfig
from repro.core.tools import DEFAULT_REGISTRY
from repro.env.tasks import make_benchmark
from repro.env.world import build_world
from repro.models.model import init_params
from repro.obs import (NULL_TRACER, MetricsRegistry, NullTracer,
                       StatsView, Tracer, percentile)
from repro.obs import tracer as tracer_mod
from repro.obs.export import (TICK_US, chrome_trace, dump_chrome_trace,
                              load_and_validate, validate_chrome_trace)
from repro.serving.cluster import EngineCluster
from repro.serving.engine import ENGINE_STAT_KEYS, InferenceEngine
from repro.serving.pipeline import GeckOptPipeline, PipelineConfig
from repro.serving.workload import (WorkloadConfig, make_workload,
                                    register_workload_prefixes,
                                    uniform_mix)

# every name the engine/pipeline instrumentation may emit
EVENT_VOCAB = {
    "enqueue", "admit", "resume", "first_token", "finish", "preempt",
    "sla_expired", "kv_evict", "cow_fork", "prefill_chunk", "stall",
    "decode", "spec_round", "request", "gate", "plan", "execute_wave",
    "engine.step", "engine.admit", "engine.prefill", "engine.decode",
    "engine.sample"}


@pytest.fixture(scope="module")
def planner():
    cfg = get_smoke_config("planner-proxy-100m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def base_engine(planner):
    cfg, params = planner
    return InferenceEngine(cfg, params, max_batch=2, cache_len=128)


def make_engine(planner, base, **kw):
    cfg, params = planner
    kw.setdefault("max_batch", 2)
    kw.setdefault("cache_len", 128)
    eng = InferenceEngine(cfg, params, **kw)
    if kw["cache_len"] == base.cache_len:
        eng._prefill, eng._decode, eng._extend = \
            base._prefill, base._decode, base._extend
    return eng


def serve_prompts(eng, n=3, max_new=6, temperature=0.8):
    from repro.serving.sampling import SamplerConfig
    for i in range(n):
        eng.add_request(f"trace probe request number {i}",
                        max_new_tokens=max_new,
                        sampler=SamplerConfig(temperature=temperature,
                                              seed=7 + i))
    return eng.run_until_done()


# ------------------------------------------------------ tracer semantics ----

def test_tracer_seq_strictly_increasing_and_tick_stamped():
    t = Tracer()
    h = t.begin("request", tick=0, group=0, lane=1, request=5)
    t.event("first_token", tick=2, group=0, lane=1, request=5)
    t.end(h, tick=4, tokens=3)
    seqs = [r.seq for r in t.records]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert [r.ph for r in t.records] == ["B", "i", "E"]
    # the end record reuses the begin's identity for pairing
    assert t.records[2].name == "request"
    assert t.records[2].lane == 1
    assert t.open_spans() == []


def test_tracer_args_are_key_sorted_and_wall_free_by_default():
    t = Tracer()
    t.event("enqueue", tick=0, zebra=1, alpha=2)
    (rec,) = t.records
    assert rec.args == (("alpha", 2), ("zebra", 1))
    assert rec.wall is None          # no clock bound -> byte-stable
    # bind_clock(None) is a no-op: the engine always forwards its
    # clock=, tracers only go wall when a REAL clock arrives
    t.bind_clock(None)
    t.event("enqueue", tick=1)
    assert t.records[1].wall is None
    t.bind_clock(lambda: 12.5)
    t.event("enqueue", tick=2)
    assert t.records[2].wall == 12.5


def test_tracer_end_before_begin_tick_rejected():
    t = Tracer()
    h = t.begin("request", tick=5)
    with pytest.raises(ValueError, match="before its begin"):
        t.end(h, tick=3)


def test_tracer_lane_of_and_clear():
    t = Tracer()
    h = t.begin("request", tick=0, lane=1)
    assert t.lane_of(h) == 1
    assert t.lane_of(12345) is None
    t.clear()
    assert t.records == () and t.open_spans() == []
    assert t.begin("request", tick=0) == 0      # seq restarts


def test_null_tracer_is_inert():
    nt = NullTracer()
    assert not nt.enabled and nt.records == ()
    assert nt.event("enqueue", tick=0) == -1
    h = nt.begin("request", tick=0)
    nt.end(h, tick=1)
    nt.bind_clock(lambda: 1.0)
    assert nt.records == () and nt.open_spans() == []
    assert not NULL_TRACER.enabled


class _RecordingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter, exit and
    closing metadata."""
    calls = []

    def __init__(self, name, **args):
        self.name = name
        self.calls.append(("init", name, args))

    def __enter__(self):
        self.calls.append(("enter", self.name))
        return self

    def set_metadata(self, **args):
        self.calls.append(("meta", self.name, args))

    def __exit__(self, *exc):
        self.calls.append(("exit", self.name))


def test_tracer_span_writes_records_and_enters_annotation(monkeypatch):
    _RecordingAnnotation.calls = []
    monkeypatch.setattr(tracer_mod, "TraceAnnotation", _RecordingAnnotation)
    t = Tracer(clock=iter([1.0, 2.5]).__next__)
    with t.span("engine.step", tick=4, group=0, lane="engine",
                step_no=4) as sp:
        sp.set(decoded=3)
    b, e = t.records
    assert (b.ph, b.name, b.tick, b.lane, b.args, b.wall) == \
        ("B", "engine.step", 4, "engine", (("step_no", 4),), 1.0)
    assert (e.ph, e.name, e.tick, e.args, e.wall) == \
        ("E", "engine.step", 4, (("decoded", 3),), 2.5)
    assert t.open_spans() == []
    # the annotation opens before the B record and closes after the E
    assert _RecordingAnnotation.calls == [
        ("init", "engine.step", {"step_no": 4}),
        ("enter", "engine.step"),
        ("meta", "engine.step", {"decoded": 3}),
        ("exit", "engine.step")]


def test_null_tracer_span_records_nothing(monkeypatch):
    _RecordingAnnotation.calls = []
    monkeypatch.setattr(tracer_mod, "TraceAnnotation", _RecordingAnnotation)
    nt = NullTracer()
    ctx = nt.span("engine.step", tick=0, lane="engine", step_no=0)
    with ctx as sp:
        sp.set(decoded=1)
    # one shared inert context, no records, no annotation
    assert nt.span("engine.decode", tick=1) is ctx
    assert nt.records == () and _RecordingAnnotation.calls == []


def test_chrome_export_places_wall_stamped_records_in_wall_time():
    t = Tracer(clock=iter([10.0, 10.25, 10.5]).__next__)
    t.event("enqueue", tick=0, lane="queue")
    with t.span("engine.step", tick=0, lane="engine"):
        pass
    doc = chrome_trace(t)
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert [e["ts"] for e in evs] == [0.0, 250000.0, 500000.0]
    assert doc["otherData"] == {"wall_origin": 10.0}
    assert validate_chrome_trace(doc) == []
    # any record without a wall stamp keeps the whole trace on ticks
    mixed = list(t.records) + [tracer_mod.TraceRecord(
        seq=3, ph="i", name="stall", tick=1, group=0, lane="engine")]
    assert [e["ts"] for e in chrome_trace(mixed)["traceEvents"]
            if e["ph"] != "M"] == [0, 1, 2, TICK_US]


# ------------------------------------------------------ metrics registry ----

def test_registry_get_or_create_and_label_identity():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    assert reg.counter("a") is not reg.counter("a", replica=0)
    assert reg.counter("a", replica=0, x=1) \
        is reg.counter("a", x=1, replica=0)    # label order-insensitive
    reg.counter("a").inc(3)
    reg.gauge("g").max(5)
    reg.gauge("g").max(2)                      # peak keeps 5
    reg.histogram("h").observe(1.0)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 5
    assert snap["histograms"]["h"]["count"] == 1


def test_empty_histogram_reports_none_never_zero():
    reg = MetricsRegistry()
    h = reg.histogram("ttft")
    assert h.mean() is None and h.percentile(50) is None
    assert percentile([], 95) is None
    snap = reg.snapshot()["histograms"]["ttft"]
    assert snap["mean"] is None and snap["p50"] is None
    assert snap["count"] == 0
    h.observe(4.0)
    assert h.percentile(50) == 4.0


def test_labeled_registry_reset_scopes_to_its_own_metrics():
    reg = MetricsRegistry()
    r0, r1 = reg.labeled(replica=0), reg.labeled(replica=1)
    r0.counter("admissions").inc(2)
    r1.counter("admissions").inc(5)
    r0.reset()
    assert r0.counter("admissions").value == 0
    assert r1.counter("admissions").value == 5  # sibling slice intact
    snap = reg.snapshot()
    assert snap["counters"]["admissions{replica=1}"] == 5


def test_stats_view_is_dict_compatible():
    reg = MetricsRegistry()
    view = StatsView(reg, ("a", "b"))
    view["a"] += 2
    view["b"] = 7
    assert dict(view) == {"a": 2, "b": 7}
    assert {**view, "c": 1} == {"a": 2, "b": 7, "c": 1}
    assert view == {"a": 2, "b": 7}
    assert "a" in view and view.get("zz", -1) == -1
    assert list(view.keys()) == ["a", "b"]     # declaration order
    # late-declared keys join the view (and its reset sweep)
    view["late"] = 9
    assert reg.counter("late").value == 9
    view.reset()
    assert view.values() == [0, 0, 0]


# ------------------------------------------- engine lifecycle tracing ------

def test_engine_defaults_to_null_tracer(planner, base_engine):
    eng = make_engine(planner, base_engine)
    assert eng.tracer is NULL_TRACER
    serve_prompts(eng, n=1)
    assert eng.tracer.records == ()


def test_traced_run_is_well_formed(planner, base_engine):
    t = Tracer()
    eng = make_engine(planner, base_engine, tracer=t)
    done = serve_prompts(eng, n=3)
    assert len(done) == 3 and t.records
    assert t.open_spans() == []                 # drained run: all closed
    seqs = [r.seq for r in t.records]
    assert seqs == list(range(len(seqs)))
    assert {r.name for r in t.records} <= EVENT_VOCAB
    ticks = [r.tick for r in t.records]
    assert ticks == sorted(ticks)               # stamped by a monotone clock
    # per-request lifecycle order: enqueue -> admit -> span begin ->
    # first_token -> span end, with one "request" span per residency
    for rid in (0, 1, 2):
        by_name = {}
        for r in t.records:
            if ("request", rid) in r.args and r.name != "request":
                by_name.setdefault(r.name, r.seq)
        spans = [r for r in t.records
                 if r.name == "request" and ("request", rid) in r.args]
        assert by_name["enqueue"] < by_name["admit"] \
            < by_name["first_token"]
        assert len(spans) == 1 and spans[0].ph == "B"
        assert spans[0].lane in (0, 1)          # a slot lane
    ends = [r for r in t.records if r.ph == "E" and r.name == "request"]
    assert len(ends) == 3
    assert all(dict(r.args)["reason"] in
               ("eos", "max_new_tokens") for r in ends)


def test_engine_step_phases_nest_inside_the_step(planner, base_engine):
    """One step that admits, prefills, decodes and samples: its phases
    are spans on the engine track inside ``engine.step``, whose closing
    args match the engine's counters."""
    t = Tracer()
    eng = make_engine(planner, base_engine, tracer=t, prefill_budget=2048)
    eng.add_request("trace probe request number 0", max_new_tokens=4)
    eng.add_request("trace probe request number 1", max_new_tokens=4)
    before = dict(eng.stats)
    t.clear()
    eng.step()
    recs = [r for r in t.records if r.name.startswith("engine.")]
    assert all(r.lane == "engine" and r.group == 0 and r.tick == 0
               for r in recs)
    assert recs[0].name == recs[-1].name == "engine.step"
    assert (recs[0].ph, recs[-1].ph) == ("B", "E")
    inner = [(r.ph, r.name) for r in recs[1:-1]]
    phases = [n for ph, n in inner if ph == "B"]
    assert phases[0] == "engine.admit"
    assert {"engine.prefill", "engine.decode", "engine.sample"} \
        <= set(phases)
    assert phases.index("engine.decode") + 1 \
        == phases.index("engine.sample")
    # the phases pair up in order: none straddles another
    stack = []
    for ph, n in inner:
        if ph == "B":
            stack.append(n)
        else:
            assert stack.pop() == n
    assert stack == []
    kinds = [dict(r.args)["kind"] for r in recs
             if r.name == "engine.prefill" and r.ph == "B"]
    assert kinds == ["head", "complete", "head", "complete"]
    step_b, step_e = dict(recs[0].args), dict(recs[-1].args)
    assert step_b == {"step_no": 0, "queued": 2}
    assert step_e == {
        "admitted": eng.stats["admissions"] - before["admissions"],
        "chunks": eng.stats["prefill_chunks"] - before["prefill_chunks"],
        "decoded": eng.stats["tokens_generated"]
        - before["tokens_generated"]}
    assert step_e["admitted"] == 2 and step_e["decoded"] == 2


def test_engine_programs_carry_stable_names(planner, base_engine):
    eng = make_engine(planner, base_engine)
    batch = {"tokens": jnp.zeros((eng.max_batch, 1), jnp.int32)}
    text = eng._decode.lower(eng.params, eng.cache, batch).as_text()
    assert text.startswith("module @jit_engine_decode")
    text = eng._prefill.lower(
        eng.params, {"tokens": jnp.zeros((1, 8), jnp.int32)}).as_text()
    assert text.startswith("module @jit_engine_prefill")


def test_queue_and_prefill_waits_read_the_injected_clock(planner,
                                                          base_engine):
    # the clock's readings in turn: enqueue, admission, first token
    reads = iter([100.0, 100.75, 101.0])
    eng = make_engine(planner, base_engine, clock=lambda: next(reads),
                      prefill_budget=1024)
    eng.add_request("trace probe request number 0", max_new_tokens=4)
    eng.step()
    q = eng.metrics.histogram("queue_wait_s")
    p = eng.metrics.histogram("prefill_wait_s")
    assert q.values == [pytest.approx(0.75)]
    assert p.values == [pytest.approx(0.25)]
    (req,) = [r for r in eng.slots if r is not None]
    assert (req.enqueue_t, req.admit_t, req.first_token_t) == \
        (100.0, 100.75, 101.0)
    eng.reset()
    assert q.count == p.count == 0


def test_tokens_bitwise_identical_tracer_on_vs_off(planner, base_engine):
    out = []
    for tracer in (None, Tracer()):
        eng = make_engine(planner, base_engine, tracer=tracer)
        done = serve_prompts(eng, n=3, temperature=0.8)
        out.append({r.request_id: list(r.output) for r in done})
    assert out[0] == out[1]


def test_same_seed_engine_trace_byte_identical(planner, base_engine,
                                               tmp_path):
    paths = []
    for i in range(2):
        t = Tracer()
        eng = make_engine(planner, base_engine, tracer=t)
        serve_prompts(eng, n=3)
        paths.append(dump_chrome_trace(t, tmp_path / f"run{i}.json"))
    assert paths[0].read_bytes() == paths[1].read_bytes()
    # tick-only: every event sits on the tick timeline
    doc = json.loads(paths[0].read_text())
    assert doc["otherData"] == {"tick_us": TICK_US}


def test_perfetto_export_round_trip(planner, base_engine, tmp_path):
    t = Tracer()
    eng = make_engine(planner, base_engine, tracer=t)
    serve_prompts(eng, n=2)
    path = dump_chrome_trace(t, tmp_path / "trace.json")
    doc, errors = load_and_validate(path)
    assert errors == []
    events = doc["traceEvents"]
    # metadata names every process and event track
    meta = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in meta} == {"process_name", "thread_name"}
    assert meta[0]["args"]["name"] == "replica 0"
    lanes = {e["args"]["name"] for e in meta
             if e["name"] == "thread_name"}
    assert "queue" in lanes and "engine" in lanes
    # every record round-trips as one event, in seq order
    recs = [e for e in events if e["ph"] != "M"]
    assert len(recs) == len(t.records)
    assert [e["args"]["seq"] for e in recs] == list(range(len(recs)))
    assert recs[0]["name"] == "enqueue"


def test_validator_catches_malformed_documents():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"nope": 1}) != []
    bad_pair = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
         "args": {"name": "p"}},
        {"ph": "M", "name": "thread_name", "pid": 0, "tid": 0,
         "args": {"name": "t"}},
        {"ph": "E", "name": "request", "pid": 0, "tid": 0, "ts": 1}]}
    assert any("E with no open B" in e
               for e in validate_chrome_trace(bad_pair))
    unclosed = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
         "args": {"name": "p"}},
        {"ph": "M", "name": "thread_name", "pid": 0, "tid": 0,
         "args": {"name": "t"}},
        {"ph": "B", "name": "request", "pid": 0, "tid": 0, "ts": 1}]}
    assert any("unclosed" in e for e in validate_chrome_trace(unclosed))
    decreasing = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
         "args": {"name": "p"}},
        {"ph": "M", "name": "thread_name", "pid": 0, "tid": 0,
         "args": {"name": "t"}},
        {"ph": "i", "name": "a", "pid": 0, "tid": 0, "ts": 5, "s": "t"},
        {"ph": "i", "name": "b", "pid": 0, "tid": 0, "ts": 2, "s": "t"}]}
    assert any("decreases" in e
               for e in validate_chrome_trace(decreasing))
    unnamed = {"traceEvents": [
        {"ph": "i", "name": "a", "pid": 3, "tid": 0, "ts": 0, "s": "t"}]}
    errs = validate_chrome_trace(unnamed)
    assert any("no process_name" in e for e in errs)
    assert any("no thread_name" in e for e in errs)


# ----------------------------------------------------------- reset audit ----

def test_engine_stat_keys_pinned():
    """The full counter surface engine.reset() must zero. Adding a stat
    to the engine without extending this pin (and therefore the reset
    sweep assertions below) fails here on purpose."""
    assert set(ENGINE_STAT_KEYS) == {
        "decode_steps", "prefills", "tokens_generated", "prefix_hits",
        "prefix_tokens_saved", "admissions", "prefix_registrations",
        "preemptions", "resumes", "prefix_evictions", "prefill_chunks",
        "stall_ticks", "sla_expired", "spec_rounds", "spec_drafted",
        "spec_accepted"}
    assert len(ENGINE_STAT_KEYS) == len(set(ENGINE_STAT_KEYS))


def test_engine_reset_zeroes_full_counter_surface(planner, base_engine):
    eng = make_engine(planner, base_engine)
    serve_prompts(eng, n=3)
    assert eng.stats["admissions"] == 3
    assert eng.stats["tokens_generated"] > 0
    assert set(eng.stats.keys()) >= set(ENGINE_STAT_KEYS)
    eng.reset()
    assert all(v == 0 for v in eng.stats.values())
    snap = eng.metrics.snapshot()
    leaked = {k: v for k, v in snap["counters"].items() if v != 0}
    assert leaked == {}, f"counters surviving reset: {leaked}"
    assert all(h["count"] == 0 for h in snap["histograms"].values())
    # a reset engine serves warm and re-accumulates from zero
    serve_prompts(eng, n=1)
    assert eng.stats["admissions"] == 1


def test_cluster_reset_zeroes_registry_and_replica_slices(planner):
    cfg, params = planner
    cluster = EngineCluster(cfg, params, 2, max_batch=2, cache_len=192,
                            seed=0)
    reqs = make_workload(WorkloadConfig(
        n_sessions=4, seed=2, intent_mix=uniform_mix(),
        profile="poisson", max_turns=1, max_new_tokens=3,
        temperature=0.8))
    register_workload_prefixes(cluster, reqs)
    stats = cluster.run_workload(reqs)
    assert stats.summary()["finished"] == len(reqs)
    snap = cluster.metrics.snapshot()
    assert snap["counters"]["cluster_requests_routed"] == len(reqs)
    assert snap["counters"]["admissions{replica=0}"] \
        + snap["counters"]["admissions{replica=1}"] >= len(reqs)
    assert snap["histograms"]["cluster_ttft_ticks"]["count"] == len(reqs)
    cluster.reset()
    snap = cluster.metrics.snapshot()
    assert all(v == 0 for v in snap["counters"].values())
    assert all(h["count"] == 0 for h in snap["histograms"].values())
    # kv gauges are re-published by the recreated pools, never negative
    assert all(v >= 0 for v in snap["gauges"].values())
    for e in cluster.replicas:
        assert all(v == 0 for v in e.stats.values())


# ------------------------------------------------------- cluster tracing ----

@pytest.fixture(scope="module")
def traced_workload():
    return make_workload(WorkloadConfig(
        n_sessions=6, seed=4, intent_mix=uniform_mix(),
        profile="poisson", max_turns=1, max_new_tokens=4,
        temperature=0.8))


@pytest.fixture(scope="module")
def cluster_pool(planner):
    cfg, params = planner
    return EngineCluster(cfg, params, 2, max_batch=2, cache_len=192,
                         seed=0).replicas


def run_cluster(pool, reqs, tracer):
    for e in pool:
        e.reset()
    cluster = EngineCluster(engines=pool, router="intent_affinity",
                            tracer=tracer)
    register_workload_prefixes(cluster, reqs)
    return cluster.run_workload(reqs)


def test_cluster_trace_byte_identical_and_tokens_unperturbed(
        cluster_pool, traced_workload, tmp_path):
    """The acceptance criteria in one place: a fixed-seed 2-replica
    run traces byte-identically across invocations, the export
    validates, and tokens match the untraced run bitwise."""
    outs, paths = [], []
    for i in range(2):
        t = Tracer()
        stats = run_cluster(cluster_pool, traced_workload, t)
        outs.append(stats.outputs())
        assert t.open_spans() == []
        paths.append(dump_chrome_trace(t, tmp_path / f"c{i}.json"))
        if i == 0:
            doc, errors = load_and_validate(paths[0])
            assert errors == []
            pids = {e["pid"] for e in doc["traceEvents"]}
            assert pids == {0, 1}       # one Perfetto process per replica
            groups = {r.group for r in t.records}
            assert groups == {0, 1}     # both replicas actually traced
    assert paths[0].read_bytes() == paths[1].read_bytes()
    # tracer off (NULL_TRACER wipes the shared pool's tracer hookup)
    untraced = run_cluster(cluster_pool, traced_workload, NULL_TRACER)
    assert untraced.outputs() == outs[0] == outs[1]


# ------------------------------------------------------ pipeline tracing ----

def test_pipeline_spans_share_the_trace(planner):
    world = build_world(0)
    tasks = make_benchmark(world, 6)
    imap = build_intent_map(tasks, DEFAULT_REGISTRY)
    gate = IntentGate(imap, ScriptedIntentClassifier(
        0.97, np.random.default_rng(0)), DEFAULT_REGISTRY.libraries())
    agent = Agent(DEFAULT_REGISTRY, world,
                  PlannerConfig(mode="react", few_shot=False),
                  gate=gate, seed=0)
    t = Tracer()
    pipe = GeckOptPipeline(agent, PipelineConfig(max_concurrent=4),
                           tracer=t)
    results = pipe.run(tasks)
    assert len(results) == len(tasks)
    assert t.open_spans() == []
    names = {r.name for r in t.records}
    assert {"gate", "plan"} <= names
    assert all(r.group == "pipeline" for r in t.records)
    gates = [r for r in t.records if r.name == "gate" and r.ph == "B"]
    assert sum(dict(r.args)["batch"] for r in gates) == len(tasks)
    assert pipe.stats.gate_batches == len(gates)
    # registry-backed PipelineStats: summary matches the span record
    ps = pipe.stats.summary()
    assert ps["admitted"] == len(tasks)
    assert ps["mean_gate_batch"] == pytest.approx(
        len(tasks) / len(gates))
    # the whole doc still validates with string group/lane labels
    assert validate_chrome_trace(chrome_trace(t)) == []


def test_pipeline_stats_empty_summary_uses_none():
    from repro.serving.pipeline import PipelineStats
    ps = PipelineStats()
    assert ps.summary()["mean_gate_batch"] is None
    ps.observe_gate_batch(4)
    assert ps.summary()["mean_gate_batch"] == 4.0
    assert ps.gate_batch_sizes == [4]
