"""End-to-end demo: the concurrent GeckOpt serving pipeline.

Composes every layer of the batched serving story:

  * a ``BatchedNeuralIntentClassifier`` gates each admission wave in ONE
    jitted (Q*8, L) forward pass of the planner-proxy LM;
  * ``GeckOptPipeline`` runs N Copilot sessions through gate → plan →
    execute concurrently (round-robin planner steps);
  * an ``InferenceEngine`` serves each session's first planner turn with
    per-intent prompt-prefix caching — sessions gated to the same intent
    reuse one cached prefill of the gated system prompt + catalog.

  PYTHONPATH=src python examples/serve_pipeline.py --smoke [--requests 12]

Without ``--smoke`` the planner serves at its full published widths.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.agent import Agent
from repro.core.catalog import (build_catalog, catalog_intent_libraries,
                                catalog_intent_map)
from repro.core.gate import IntentGate
from repro.core.intents import INTENTS, build_intent_map
from repro.core.planner import PlannerConfig
from repro.core.retriever import ToolRetriever
from repro.core.tools import DEFAULT_REGISTRY
from repro.env.evaluator import evaluate_results
from repro.env.tasks import make_benchmark
from repro.env.world import build_world
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import count_params_analytic, init_params
from repro.serving.cluster import EngineCluster, ROUTER_POLICIES
from repro.serving.engine import InferenceEngine
from repro.serving.neural_planner import BatchedNeuralIntentClassifier
from repro.serving.pipeline import GeckOptPipeline, PipelineConfig


def main(argv=None):
    """Run the demo; returns the session count, wall time and the
    pipeline's and engine's stats."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="planner-proxy-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the architecture's small smoke variant")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--backend", default=None,
                    choices=("reference", "pallas"),
                    help="kernel backend for the engine's jitted steps")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve planner turns on an EngineCluster of N "
                         "replicas instead of one engine")
    ap.add_argument("--router", default="intent_affinity",
                    choices=ROUTER_POLICIES,
                    help="cluster routing policy (with --replicas > 1)")
    ap.add_argument("--kv-mode", default="dense",
                    choices=("dense", "paged"),
                    help="KV-cache manager for the engine(s): dense "
                         "slabs or the paged pool with CoW prefix "
                         "sharing")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="paged: physical KV blocks per engine "
                         "(default: the dense budget)")
    ap.add_argument("--block-size", type=int, default=None,
                    help="paged: tokens per KV block (default: 16)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative decoding for planner turns: draft "
                         "--draft-k tokens per slot, verify in one "
                         "target forward (tokens stay bitwise "
                         "identical; the draft shares the target's "
                         "weights here)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens per speculative round (>= 1)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="chunked prefill: max prompt tokens per engine "
                         "step, interleaved with decode (stall-free "
                         "scheduling; default: monolithic prefill). "
                         "Useful here — planner prompts carry ~2.5k-"
                         "token intent catalogs")
    ap.add_argument("--admission", default="fifo",
                    choices=("fifo", "slack"),
                    help="admission-queue order: arrival or earliest "
                         "SLA deadline first")
    ap.add_argument("--catalog-size", type=int, default=None,
                    help="serve a generated tool catalog of N tools "
                         "(core/catalog.py; default: the base "
                         "registry)")
    ap.add_argument("--retriever-k", type=int, default=None,
                    help="expose only the retrieved top-k toolset per "
                         "request (core/retriever.py) instead of the "
                         "gated library catalog; sessions retrieving "
                         "the same toolset share one engine prefix")
    ap.add_argument("--trace-out", default="",
                    help="write the unified pipeline+engine trace here "
                         "as Chrome trace-event JSON (open in Perfetto)")
    args = ap.parse_args(argv)
    if args.spec_decode and args.draft_k < 1:
        ap.error(f"--spec-decode needs --draft-k >= 1, "
                 f"got {args.draft_k}")
    if args.prefill_budget is not None and args.prefill_budget < 1:
        ap.error(f"--prefill-budget must be >= 1, "
                 f"got {args.prefill_budget}")
    if args.catalog_size is not None and args.catalog_size < 1:
        ap.error(f"--catalog-size must be >= 1, got {args.catalog_size}")
    if args.retriever_k is not None and args.retriever_k < 1:
        ap.error(f"--retriever-k must be >= 1, got {args.retriever_k}")

    enable_compile_cache()
    # --- the serving fleet: engine(s) + one batched gate model -----------
    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    params = init_params(jax.random.PRNGKey(0), cfg)
    from repro.serving.specdec import SpecConfig
    spec = (SpecConfig(draft_cfg=cfg, draft_params=params,
                       k=args.draft_k)
            if args.spec_decode else None)
    # one tracer spans the whole stack: gate/plan/execute waves land on
    # the "pipeline" track, engine lifecycle events on per-slot tracks
    from repro.obs import Tracer
    tracer = Tracer() if args.trace_out else None
    # cache_len must hold the longest per-intent planner prefix (~2.5k
    # tokens of system prompt + catalog) plus the turn suffix; generated
    # catalogs serialize wider gated-library subsets, so give them room
    cache_len = 8192 if (args.catalog_size or 0) > 48 else 4096
    if args.replicas > 1:
        engine = EngineCluster(cfg, params, args.replicas,
                               router=args.router, max_batch=4,
                               cache_len=cache_len, backend=args.backend,
                               kv_mode=args.kv_mode,
                               kv_blocks=args.kv_blocks,
                               block_size=args.block_size,
                               spec_decode=spec,
                               prefill_budget=args.prefill_budget,
                               admission=args.admission,
                               tracer=tracer)
    else:
        engine = InferenceEngine(cfg, params, max_batch=4,
                                 cache_len=cache_len, backend=args.backend,
                                 kv_mode=args.kv_mode,
                                 kv_blocks=args.kv_blocks,
                                 block_size=args.block_size,
                                 spec_decode=spec,
                                 prefill_budget=args.prefill_budget,
                                 admission=args.admission,
                                 tracer=tracer)
    classifier = BatchedNeuralIntentClassifier(cfg, params)
    print(f"planner engine up: {count_params_analytic(cfg)/1e6:.1f}M "
          f"params, {args.replicas} replica(s) x 4 slots; "
          f"batched intent gate ready")

    # --- the platform ----------------------------------------------------
    world = build_world(0)
    tasks = make_benchmark(world, args.requests)
    if args.catalog_size is not None:
        registry = build_catalog(args.catalog_size, seed=0)
        imap = catalog_intent_map(registry)
    else:
        registry = DEFAULT_REGISTRY
        imap = build_intent_map(make_benchmark(world, 64), registry)
    gate = IntentGate(imap, classifier, registry.libraries())
    retriever = None
    exposure = "gated"
    if args.retriever_k is not None:
        retriever = ToolRetriever(registry,
                                  catalog_intent_libraries(registry),
                                  k=args.retriever_k)
        exposure = "retrieved"
        print(f"toolset retrieval on: top-{args.retriever_k} of "
              f"{len(registry.tools)} tools exposed per request")
    agent = Agent(registry, world,
                  PlannerConfig(mode="react", few_shot=False),
                  gate=gate, seed=0, retriever=retriever,
                  exposure=exposure)

    # --- run everything through the concurrent pipeline ------------------
    pipe = GeckOptPipeline(
        agent, PipelineConfig(max_concurrent=args.concurrency),
        engine=engine, tracer=tracer)
    t0 = time.time()
    results = pipe.run(tasks)
    dt = time.time() - t0
    rep = evaluate_results(results, "pipeline")

    ps = pipe.stats.summary()
    es = engine.throughput_stats()
    print(f"\n{len(results)} sessions in {dt:.2f}s "
          f"({len(results)/max(dt,1e-9):.2f} tasks/s, "
          f"{args.concurrency} concurrent)")
    mgb = ps["mean_gate_batch"]          # None when no wave ran
    print(f"gate:    {ps['gate_batches']} batched calls, mean wave "
          f"{'n/a' if mgb is None else f'{mgb:.1f}'} queries "
          f"(vs {len(INTENTS)*len(results)} B=1 forwards sequentially)")
    print(f"engine:  {ps['engine_turns']} planner turns over "
          f"{len(engine.prefixes)} intent prefixes — "
          f"{es['prefix_hits']} prefix hits, "
          f"{es['prefix_tokens_saved']} prefill tokens saved, "
          f"{es['tokens_generated']} tokens decoded")
    print(f"kv[{es['kv_mode']}]: peak {es['kv_bytes_peak'] / 2**20:.1f} "
          f"MiB of {es['kv_bytes_allocated'] / 2**20:.1f} MiB"
          + (f" | shared-block frac {es['kv_shared_frac']:.2f}, "
             f"{es['preemptions']} preemptions"
             if es["kv_mode"] == "paged" else ""))
    if args.retriever_k is not None:
        print(f"retrieve: {ps['retrievals']} toolsets retrieved "
              f"(top-{args.retriever_k}), "
              f"{ps['retrieval_widens']} miss-and-widen escalations")
    if args.spec_decode:
        print(f"spec-decode[k={args.draft_k}]: "
              f"{es['tokens_per_step']:.2f} tokens/target-forward, "
              f"accept rate {es['spec_accept_rate']:.2f} over "
              f"{es['spec_rounds']} rounds")
    if args.replicas > 1:
        for r in es["per_replica"]:
            print(f"  replica {r['replica']}: {r['admissions']} turns, "
                  f"{r['prefix_hits']} prefix hits, "
                  f"{r['tokens_generated']} tokens")
    print(f"quality: success={100*rep.success_rate:.1f}% "
          f"tokens/task={rep.tokens_per_task/1000:.2f}k "
          f"steps={rep.steps_per_task:.2f} "
          f"fallback={100*rep.fallback_rate:.1f}%")
    print("(gate params are random-init here, so fallback is high — "
          "examples/train_planner.py fine-tunes the proxy into an "
          "accurate gate)")
    if tracer is not None:
        from repro.obs.export import dump_chrome_trace
        dump_chrome_trace(tracer, args.trace_out)
        print(f"trace: {len(tracer.records)} records -> "
              f"{args.trace_out}")
    return {"sessions": len(results), "wall_s": dt, "pipeline": ps,
            "engine": es}


if __name__ == "__main__":
    main()
