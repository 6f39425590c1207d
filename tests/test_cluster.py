"""Multi-replica serving cluster: router policies, exact token parity
across replica counts, affinity vs round-robin prefix-hit rates, and
pipeline-on-cluster integration."""
import os
import subprocess
import sys

import jax
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
from repro.serving.cluster import (ROUTER_POLICIES, EngineCluster,
                                   IntentAffinityRouter, ReplicaView,
                                   make_router, rendezvous_hash)
from repro.serving.workload import (WorkloadConfig, make_workload,
                                    register_workload_prefixes,
                                    skewed_mix, uniform_mix)


@pytest.fixture(scope="module")
def planner():
    cfg = get_smoke_config("planner-proxy-100m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def pool(planner):
    """Four replicas sharing one jit cache; tests reset() them."""
    cfg, params = planner
    return EngineCluster(cfg, params, 4, max_batch=2,
                         cache_len=192, seed=0).replicas


def mkcluster(pool, policy, n=None, **kw):
    engines = pool[:n] if n else pool
    for e in engines:
        e.reset()
    return EngineCluster(engines=engines, router=policy, **kw)


# ----------------------------------------------------- router unit tests ----

def views(*loads, holder=None):
    return [ReplicaView(i, busy, q, holds_prefix=(i == holder))
            for i, (busy, q) in enumerate(loads)]


def test_round_robin_cycles():
    r = make_router("round_robin")
    v = views((0, 0), (0, 0), (0, 0))
    assert [r.select(v) for _ in range(6)] == [0, 1, 2, 0, 1, 2]


def test_least_loaded_picks_min_load_ties_to_lowest_index():
    r = make_router("least_loaded")
    assert r.select(views((2, 3), (1, 0), (4, 0))) == 1
    assert r.select(views((1, 1), (0, 2), (2, 0))) == 0   # tie 2,2,2 -> 0
    # queue depth counts as load, not just busy slots
    assert r.select(views((0, 9), (1, 0))) == 1


def test_affinity_routes_to_prefix_holder():
    r = make_router("intent_affinity")
    # holder wins even when busier
    assert r.select(views((4, 6), (0, 0), (0, 0), holder=0), "k") == 0
    # no key -> least loaded
    assert r.select(views((4, 6), (1, 0), (0, 0), holder=0)) == 2
    # no holder -> deterministic rendezvous placement over all replicas
    home = rendezvous_hash("k", range(3))
    assert r.select(views((0, 0), (0, 0), (0, 0)), "k") == home
    assert r.select(views((3, 5), (3, 5), (3, 5)), "k") == home


def test_affinity_spills_when_home_overloaded():
    r = IntentAffinityRouter(spill_load=8)
    assert r.select(views((4, 3), (0, 0), holder=0), "k") == 0   # 7 < 8
    assert r.select(views((4, 4), (0, 0), holder=0), "k") == 1   # 8 >= 8


def test_rendezvous_hash_stable_and_spreading():
    keys = [f"intent:{i}" for i in range(16)]
    homes = {k: rendezvous_hash(k, range(4)) for k in keys}
    assert homes == {k: rendezvous_hash(k, range(4)) for k in keys}
    assert len(set(homes.values())) >= 3       # keys spread over replicas
    # adding a replica only remaps keys the new replica wins
    grown = {k: rendezvous_hash(k, range(5)) for k in keys}
    assert all(grown[k] in (homes[k], 4) for k in keys)


def test_unknown_policy_rejected():
    with pytest.raises(ValueError):
        make_router("random")


def test_prebuilt_engines_reject_sizing_kwargs(pool):
    """engines= keeps the replicas' own configuration; sizing kwargs
    would be silently dropped, so the constructor refuses them."""
    with pytest.raises(ValueError, match="mutually exclusive"):
        EngineCluster(engines=pool, max_batch=16)
    with pytest.raises(ValueError, match="mutually exclusive"):
        EngineCluster(engines=pool, cache_len=1024)
    with pytest.raises(ValueError, match="mutually exclusive"):
        EngineCluster(engines=pool, devices=jax.devices())


def test_replicas_committed_to_their_devices(pool):
    """Replica i's weights and caches sit on devices[i % len(devices)]
    (one device here: all of them on it)."""
    dev = jax.devices()[0]
    for e in pool:
        for tree in (e.params, e.cache):
            assert {d for leaf in jax.tree.leaves(tree)
                    for d in leaf.devices()} == {dev}


PLACEMENT_SCRIPT = r"""
import jax
from repro.configs import get_smoke_config
from repro.models.model import init_params
from repro.serving.cluster import EngineCluster
from repro.serving.workload import (WorkloadConfig, make_workload,
                                    register_workload_prefixes, uniform_mix)
cfg = get_smoke_config("planner-proxy-100m")
params = init_params(jax.random.PRNGKey(0), cfg)
reqs = make_workload(WorkloadConfig(n_sessions=6, seed=1, max_turns=2,
                                    intent_mix=uniform_mix(),
                                    max_new_tokens=3, temperature=0.8))
outs = []
for devs in (jax.devices(), jax.devices()[:1]):
    cl = EngineCluster(cfg, params, 4, router="intent_affinity",
                       max_batch=2, cache_len=192, devices=devs)
    for i, e in enumerate(cl.replicas):
        for tree in (e.params, e.cache):
            assert {d for l in jax.tree.leaves(tree) for d in l.devices()}                 == {devs[i % len(devs)]}
    register_workload_prefixes(cl, reqs)
    outs.append(cl.run_workload(reqs).outputs())
assert len(outs[0]) == len(reqs) and outs[0] == outs[1]
print("PLACEMENT_OK")
"""


def test_four_device_placement_keeps_tokens():
    """On four devices each replica runs on its own; the tokens equal
    those of the same seeded workload with all replicas on one device.
    Runs in a subprocess: the device count locks at JAX start-up."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run([sys.executable, "-c", PLACEMENT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "PLACEMENT_OK" in out.stdout, (out.stdout[-2000:],
                                          out.stderr[-2000:])


# ------------------------------------------------- exact token parity ------

def test_token_parity_one_replica_vs_four_all_policies(pool):
    """The same seeded workload (stochastic seeded samplers, multi-turn
    sessions, per-intent prefixes) replayed through 1 replica and through
    4 replicas yields identical per-request outputs under EVERY router
    policy: routing moves work, never tokens."""
    reqs = make_workload(WorkloadConfig(
        n_sessions=8, seed=2, intent_mix=uniform_mix(),
        profile="poisson", max_turns=2, max_new_tokens=3,
        temperature=0.8))

    def serve(policy, n):
        cl = mkcluster(pool, policy, n=n)
        register_workload_prefixes(cl, reqs)
        stats = cl.run_workload(reqs)
        return stats.outputs(), stats.summary()

    ref_out, ref_sum = serve("round_robin", 1)
    assert len(ref_out) == len(reqs) == ref_sum["finished"]
    assert all(len(v) > 0 for v in ref_out.values())
    for policy in ROUTER_POLICIES:
        out, summ = serve(policy, 4)
        assert out == ref_out, policy
        assert summ["tokens_out"] == ref_sum["tokens_out"]
        # the cluster spread the same work over more replicas
        assert sum(r["admissions"] for r in summ["per_replica"]) \
            == len(reqs)


def test_affinity_beats_round_robin_on_skewed_mix(pool):
    """On a skewed intent mix at 4 replicas, consistent-hash affinity
    achieves a strictly higher prefix-hit ratio than round-robin (the
    prefix lives on ONE home replica; oblivious routing misses it on
    the other three) — with identical generated tokens."""
    reqs = make_workload(WorkloadConfig(
        n_sessions=16, seed=0, intent_mix=skewed_mix(hot_frac=0.7),
        profile="poisson", max_turns=2, max_new_tokens=3,
        temperature=0.8))

    def serve(policy):
        cl = mkcluster(pool, policy)
        register_workload_prefixes(cl, reqs)
        stats = cl.run_workload(reqs)
        return stats.summary(), stats.outputs()

    rr, rr_out = serve("round_robin")
    aff, aff_out = serve("intent_affinity")
    assert aff["prefix_hit_ratio"] > rr["prefix_hit_ratio"]
    assert aff["prefix_hit_ratio"] == 1.0     # every request rode its home
    assert rr["prefix_hit_ratio"] <= 0.5
    assert aff_out == rr_out
    assert aff["tokens_out"] == rr["tokens_out"]
    # affinity concentrated the hot intent: per-replica hit rates prove
    # the home replica served hits while others served their own intents
    assert all(r["prefix_hits"] == r["admissions"]
               for r in aff["per_replica"] if r["admissions"])


def test_least_loaded_spreads_bursts(pool):
    """A burst of simultaneous arrivals lands across all replicas under
    least_loaded (each submission sees the previous one's queue)."""
    reqs = make_workload(WorkloadConfig(
        n_sessions=12, seed=4, profile="bursty", burst_size=12,
        inter_arrival=1.0, max_new_tokens=2))
    cl = mkcluster(pool, "least_loaded")
    stats = cl.run_workload(reqs)
    s = stats.summary()
    assert s["finished"] == len(reqs)
    assert all(r["admissions"] >= 2 for r in s["per_replica"])
    assert all(r["utilization"] > 0 for r in s["per_replica"])


def test_cluster_stats_schema(pool):
    """Latency/queue metrics are well-formed ticks and SLA accounting
    covers every finished request."""
    reqs = make_workload(WorkloadConfig(
        n_sessions=6, seed=1, max_new_tokens=2, sla_ticks=64))
    cl = mkcluster(pool, "intent_affinity")
    register_workload_prefixes(cl, reqs)
    stats = cl.run_workload(reqs)
    s = stats.summary()
    assert s["finished"] == s["requests"] == len(reqs)
    assert 1 <= s["ttft_p50"] <= s["ttft_p95"] <= s["e2e_p95"]
    assert 0 <= s["queue_wait_p50"] <= s["queue_wait_p95"]
    assert s["sla_attainment"] == 1.0        # tiny load, generous SLA
    assert s["tokens_out"] >= s["tokens_decoded"] > 0
    for t in stats.traces:
        assert t.finish_tick >= t.admit_tick >= t.arrival_tick
        assert t.request.finish_reason is not None


def test_utilization_bounded_by_one(pool):
    """Terminal-at-admission floods (max_new_tokens=1 drains the whole
    queue through one slot per tick) must not overcount busy-slot-ticks:
    utilization stays in [0, 1]."""
    reqs = make_workload(WorkloadConfig(n_sessions=8, seed=6,
                                        inter_arrival=0.0,
                                        max_new_tokens=1))
    cl = mkcluster(pool, "least_loaded", n=1)
    s = cl.run_workload(reqs).summary()
    assert s["finished"] == len(reqs)
    assert all(0.0 <= r["utilization"] <= 1.0 for r in s["per_replica"])


def test_sla_counts_unfinished_as_misses(pool):
    """Cutting a run off at max_ticks leaves deadline-carrying requests
    unfinished; they count as SLA misses, not silently dropped."""
    reqs = make_workload(WorkloadConfig(n_sessions=8, seed=6,
                                        inter_arrival=0.0, max_turns=2,
                                        max_new_tokens=8, sla_ticks=4))
    cl = mkcluster(pool, "least_loaded", n=1)
    s = cl.run_workload(reqs, max_ticks=2).summary()
    assert s["finished"] < s["requests"]
    # the whole workload is accounted for, including follow-up turns
    # never released before the cutoff
    assert s["requests"] == len(reqs)
    assert s["sla_attainment"] < 1.0


def test_run_workload_requires_fresh_cluster_and_reset_recycles(pool):
    """Back-to-back run_workload on one cluster would silently mix runs
    in ClusterStats — it must refuse; cluster.reset() recycles the whole
    fleet and reproduces a fresh cluster's run exactly."""
    reqs = make_workload(WorkloadConfig(n_sessions=5, seed=9,
                                        max_new_tokens=2,
                                        temperature=0.8))
    cl = mkcluster(pool, "intent_affinity")
    register_workload_prefixes(cl, reqs)
    first = cl.run_workload(reqs)
    with pytest.raises(RuntimeError):
        cl.run_workload(reqs)
    cl.reset()
    assert cl.is_idle() and cl.tick == 0 and not cl.prefixes
    register_workload_prefixes(cl, reqs)
    again = cl.run_workload(reqs)
    assert again.outputs() == first.outputs()
    assert again.summary() == first.summary()


def test_run_workload_rejects_orphaned_followups(pool):
    """A follow-up turn whose predecessor never runs can never be
    released — fail fast instead of spinning to max_ticks."""
    reqs = make_workload(WorkloadConfig(n_sessions=4, seed=3,
                                        max_turns=3, max_new_tokens=2))
    orphans = [w for w in reqs if w.turn > 0]
    assert orphans, "need multi-turn sessions for this test"
    cl = mkcluster(pool, "least_loaded")
    with pytest.raises(ValueError, match="predecessor"):
        cl.run_workload(orphans)


# -------------------------------------------------- pipeline integration ----

def test_pipeline_targets_cluster(planner):
    """GeckOptPipeline(engine=EngineCluster) serves every session's
    planner turn with per-intent prefix caching on the session's home
    replica — same surface as the single engine."""
    cfg, params = planner
    world = build_world(0)
    tasks = make_benchmark(world, 4)
    imap = build_intent_map(make_benchmark(world, 32), DEFAULT_REGISTRY)
    gate = IntentGate(imap, ScriptedIntentClassifier(
        1.0, np.random.default_rng(0)), DEFAULT_REGISTRY.libraries())
    agent = Agent(DEFAULT_REGISTRY, world,
                  PlannerConfig(mode="cot", few_shot=False), gate=gate,
                  seed=0)
    cluster = EngineCluster(cfg, params, 2, router="intent_affinity",
                            max_batch=2, cache_len=4096)

    from repro.serving.pipeline import GeckOptPipeline, PipelineConfig
    pipe = GeckOptPipeline(agent,
                           PipelineConfig(max_concurrent=4,
                                          engine_max_new_tokens=2),
                           engine=cluster)
    results = pipe.run(tasks)
    assert len(results) == 4
    assert pipe.stats.engine_replicas == 2
    assert pipe.stats.engine_turns == 4
    agg = cluster.throughput_stats()
    # every planner turn was admitted somewhere and rode a prefix
    assert agg["admissions"] == 4
    assert agg["prefix_hits"] == 4
    assert len(cluster.prefixes) <= 4
    assert all(es.idle for es in pipe._engine_sessions)
    assert cluster.is_idle()
