"""Serving launcher: run the inference engine with batched requests.

  PYTHONPATH=src python -m repro.launch.serve --arch planner-proxy-100m \
      --smoke --requests 16 --max-new 24

With ``--replicas N`` the launcher serves a synthetic mixed-intent
workload (serving/workload.py) on an N-replica ``EngineCluster``
instead, and reports cluster-level tick metrics:

  PYTHONPATH=src python -m repro.launch.serve --smoke --replicas 4 \
      --router intent_affinity --requests 32 --profile bursty --skew 0.7

``--spec-decode`` turns on speculative decoding (serving/specdec.py):
the engine drafts ``--draft-k`` greedy tokens per slot and verifies
them in one target forward, emitting a multiple of the tokens per
target forward with tokens bitwise identical to non-speculative
decoding (unconditionally at T=0; at any temperature for seeded
requests — DESIGN.md §Speculative decoding). The launcher has no trained draft
checkpoint to load, so the draft shares the target's weights — the
perfect-agreement stand-in the benches use; point a real deployment's
``SpecConfig`` at a distilled ``planner_proxy_100m``-class draft.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_params
from repro.obs import Tracer
from repro.obs.export import dump_chrome_trace
from repro.serving.cluster import ROUTER_POLICIES, EngineCluster
from repro.serving.engine import InferenceEngine
from repro.serving.sched import ADMISSION_POLICIES
from repro.serving.sampling import SamplerConfig
from repro.serving.specdec import SpecConfig
from repro.serving.workload import (PROFILES, WorkloadConfig,
                                    make_workload,
                                    register_workload_prefixes,
                                    skewed_mix, uniform_mix)
from repro.training.checkpoint import load_checkpoint


def _fmt(v, unit: str = "") -> str:
    """Render a possibly-None metric ("n/a": empty percentile series)."""
    return "n/a" if v is None else f"{v:.0f}{unit}"


def serve_cluster(cfg, params, args, spec_decode=None):
    # cluster engines run on the tick clock only, so the trace is
    # wall-free and byte-identical across same-seed runs
    tracer = Tracer() if args.trace_out else None
    cluster = EngineCluster(cfg, params, args.replicas,
                            router=args.router,
                            max_batch=args.max_batch,
                            cache_len=args.cache_len,
                            backend=args.backend,
                            kv_mode=args.kv_mode,
                            kv_blocks=args.kv_blocks,
                            block_size=args.block_size,
                            spec_decode=spec_decode,
                            prefill_budget=args.prefill_budget,
                            interleave=not args.no_interleave,
                            admission=args.admission,
                            sla_spill=args.sla_spill,
                            tracer=tracer)
    mix = (skewed_mix(hot_frac=args.skew) if args.skew > 0
           else uniform_mix())
    reqs = make_workload(WorkloadConfig(
        n_sessions=args.requests, intent_mix=mix, profile=args.profile,
        max_turns=args.turns, max_new_tokens=args.max_new,
        temperature=args.temperature, seed=0))
    register_workload_prefixes(cluster, reqs)
    t0 = time.time()
    stats = cluster.run_workload(reqs)
    dt = time.time() - t0
    s = stats.summary()
    print(f"cluster[{args.replicas}x{args.max_batch} slots, "
          f"router={args.router}] served {s['finished']}/{s['requests']} "
          f"requests in {s['ticks']} ticks ({dt:.2f}s wall)")
    print(f"ttft p50/p95/p99 {_fmt(s['ttft_p50'])}/{_fmt(s['ttft_p95'])}"
          f"/{_fmt(s['ttft_p99'])} ticks | "
          f"admit-wait p95 {_fmt(s['admit_wait_p95'])} | "
          f"e2e p50/p95 {_fmt(s['e2e_p50'])}/{_fmt(s['e2e_p95'])} | "
          f"SLA {100 * s['sla_attainment']:.1f}%"
          + (f" | {s['sla_expired']} expired in queue"
             if s["sla_expired"] else ""))
    print(f"prefix-hit ratio {s['prefix_hit_ratio']:.2f} | "
          f"{s['tokens_out']} tokens out")
    if spec_decode is not None:
        print(f"spec-decode[k={spec_decode.k}]: "
              f"{s['tokens_per_step']:.2f} tokens/target-forward over "
              f"{s['spec_rounds']} rounds, accept rate "
              f"{s['spec_accept_rate']:.2f}")
    kv_line = (f"kv[{args.kv_mode}]: peak "
               f"{s['kv_bytes_peak'] / 2**20:.1f} MiB of "
               f"{s['kv_bytes_allocated'] / 2**20:.1f} MiB")
    if args.kv_mode == "paged":
        kv_line += (f" | shared-block frac {s['kv_shared_frac']:.2f} | "
                    f"{s['preemptions']} preemptions, "
                    f"{s['resumes']} resumes, "
                    f"{s['prefix_evictions']} prefix evictions")
    print(kv_line)
    for r in s["per_replica"]:
        print(f"  replica {r['replica']}: {r['admissions']} admissions, "
              f"hit {r['hit_ratio']:.2f}, util {r['utilization']:.2f}")
    if tracer is not None:
        dump_chrome_trace(tracer, args.trace_out)
        print(f"trace: {len(tracer.records)} records -> "
              f"{args.trace_out}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="planner-proxy-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt", action="append", default=None,
                    help="single-engine mode: serve this prompt "
                         "(repeatable; one request each) instead of "
                         "--requests built-in demo prompts")
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=512)
    ap.add_argument("--temperature", type=float, default=0.7)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--backend", default=None,
                    choices=("reference", "pallas"),
                    help="kernel backend (default: PerfFlags.kernel_backend)")
    ap.add_argument("--kv-mode", default="dense",
                    choices=("dense", "paged"),
                    help="KV-cache manager: dense per-slot slabs or the "
                         "paged block pool with CoW prefix sharing")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="paged: physical KV blocks (default: the dense "
                         "budget, max_batch*cache_len/block_size)")
    ap.add_argument("--block-size", type=int, default=None,
                    help="paged: tokens per KV block (default: 16)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve an EngineCluster of N replicas (> 1)")
    ap.add_argument("--router", default="intent_affinity",
                    choices=ROUTER_POLICIES)
    ap.add_argument("--profile", default="uniform", choices=PROFILES,
                    help="workload arrival profile (cluster mode)")
    ap.add_argument("--skew", type=float, default=0.0,
                    help="hot-intent traffic fraction in [0, 1] "
                         "(0 = uniform mix, 1 = all hot)")
    ap.add_argument("--turns", type=int, default=1,
                    help="max turns per session (cluster mode)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="chunked prefill: max prompt tokens processed "
                         "per engine step (attn_chunk-aligned slabs; "
                         "budgets below one chunk fall back to one "
                         "whole chunk per step), interleaved with "
                         "decode so long prompts never stall running "
                         "streams. Default: monolithic admission-step "
                         "prefill")
    ap.add_argument("--no-interleave", action="store_true",
                    help="with --prefill-budget: run each prefill to "
                         "completion before decoding (the stall-prone "
                         "baseline the benches compare against)")
    ap.add_argument("--admission", default="fifo",
                    choices=ADMISSION_POLICIES,
                    help="admission-queue order: arrival (fifo) or "
                         "earliest SLA deadline first (slack; also "
                         "picks most-slack preemption victims)")
    ap.add_argument("--sla-spill", action="store_true",
                    help="intent_affinity router: spill a request to "
                         "the least-loaded replica when its SLA slack "
                         "is smaller than its home replica's load "
                         "(cluster mode)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative decoding: draft --draft-k greedy "
                         "tokens per slot with a draft model sharing "
                         "the target's weights, verify them in one "
                         "target forward (tokens bitwise-identical to "
                         "non-speculative decoding at --temperature 0, "
                         "and at any temperature for seeded requests — "
                         "the cluster workload path; unseeded T>0 "
                         "engine-stream sampling draws a different key "
                         "schedule, like any co-tenancy change)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens per speculative round (>= 1)")
    ap.add_argument("--catalog-size", type=int, default=None,
                    help="single-engine mode: serve against a generated "
                         "tool catalog of N tools (core/catalog.py) "
                         "instead of the base registry; needs "
                         "--retriever-k (the launcher has no intent "
                         "gate, so a scaled catalog is only servable "
                         "through retrieval)")
    ap.add_argument("--retriever-k", type=int, default=None,
                    help="single-engine mode: retrieve a top-k toolset "
                         "per request (core/retriever.py), register "
                         "each toolset as a shared engine prefix, and "
                         "prepend its catalog text to the prompt — "
                         "requests retrieving the same toolset share "
                         "one cached prefill")
    ap.add_argument("--trace-out", default="",
                    help="write the request-lifecycle and step-phase "
                         "trace here after the run, as Chrome "
                         "trace-event JSON (open in Perfetto / "
                         "chrome://tracing). Cluster traces are "
                         "tick-only and byte-identical across "
                         "same-seed runs; the single-engine path "
                         "injects time.time, so its events are placed "
                         "in wall time")
    return ap


def validate_args(ap: argparse.ArgumentParser, args):
    """CLI-level invalid-combination errors, raised before any model is
    built (mirrors the engine constructors' refusals)."""
    if not 0.0 <= args.skew <= 1.0:
        ap.error(f"--skew must be in [0, 1], got {args.skew}")
    if args.kv_mode == "dense" and (args.kv_blocks is not None
                                    or args.block_size is not None):
        ap.error("--kv-blocks/--block-size apply only to "
                 "--kv-mode paged")
    if args.spec_decode and args.draft_k < 1:
        ap.error(f"--spec-decode needs --draft-k >= 1, "
                 f"got {args.draft_k}")
    if args.replicas < 1:
        ap.error(f"--replicas must be >= 1, got {args.replicas}")
    if args.prefill_budget is not None and args.prefill_budget < 1:
        ap.error(f"--prefill-budget must be >= 1, "
                 f"got {args.prefill_budget}")
    if args.no_interleave and args.prefill_budget is None:
        ap.error("--no-interleave only applies with --prefill-budget "
                 "(monolithic prefill has nothing to interleave)")
    if args.sla_spill and args.replicas < 2:
        ap.error("--sla-spill needs --replicas >= 2 (router-level "
                 "spill has nowhere to go on one replica)")
    if args.catalog_size is not None and args.catalog_size < 1:
        ap.error(f"--catalog-size must be >= 1, got {args.catalog_size}")
    if args.retriever_k is not None and args.retriever_k < 1:
        ap.error(f"--retriever-k must be >= 1, got {args.retriever_k}")
    if args.catalog_size is not None and args.retriever_k is None:
        ap.error("--catalog-size needs --retriever-k: the launcher has "
                 "no intent gate, so a scaled catalog is only servable "
                 "through retrieved toolsets")
    if args.prompt and args.replicas > 1:
        ap.error("--prompt applies to the single-engine path; cluster "
                 "mode serves the synthetic intent workload")
    if args.retriever_k is not None and args.replicas > 1:
        ap.error("--retriever-k applies to the single-engine prompt "
                 "path; cluster mode serves the synthetic intent "
                 "workload (examples/serve_pipeline.py runs retrieval "
                 "against a cluster)")
    return args


def main(argv=None):
    """Run the launcher. The single-engine path returns its throughput
    stats plus ``requests``, ``finished``, ``wall_s`` and the generated
    token ids per request (``outputs``, in submission order)."""
    ap = build_parser()
    args = validate_args(ap, ap.parse_args(argv))
    enable_compile_cache()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.checkpoint:
        params = load_checkpoint(args.checkpoint, params)
    # no trained draft checkpoint ships with the repo: self-draft
    # (perfect agreement) stands in for a distilled small model
    spec = (SpecConfig(draft_cfg=cfg, draft_params=params,
                       k=args.draft_k)
            if args.spec_decode else None)

    if args.replicas > 1:
        serve_cluster(cfg, params, args, spec_decode=spec)
        return

    prompts = args.prompt or [
        f"Plot xview1 images around Tampa Bay with cloud cover below "
        f"{10 + i}%" for i in range(args.requests)]
    exposures = None
    if args.retriever_k is not None:
        from repro.core.catalog import (build_catalog,
                                        catalog_intent_libraries)
        from repro.core.retriever import ToolRetriever
        from repro.core.tools import DEFAULT_REGISTRY
        from repro.serving.tokenizer import TOKENIZER
        registry = (build_catalog(args.catalog_size, seed=0)
                    if args.catalog_size is not None
                    else DEFAULT_REGISTRY)
        retriever = ToolRetriever(registry,
                                  catalog_intent_libraries(registry),
                                  k=args.retriever_k)
        exposures = retriever.retrieve_batch(prompts,
                                             [None] * len(prompts))
        prefix_texts = {e.key_str: e.catalog_text(registry)
                        for e in exposures}
        # the cache must hold the widest toolset prefix + the turn;
        # grow it rather than refuse (register_prefix asserts the fit)
        need = max((len(TOKENIZER.encode(t)) + 1
                    for t in prefix_texts.values()), default=0)
        need += args.max_new + 128
        if args.cache_len < need:
            print(f"cache-len {args.cache_len} -> {need} "
                  f"(toolset prefixes need the room)")
            args.cache_len = need

    tracer = Tracer() if args.trace_out else None
    engine = InferenceEngine(cfg, params, max_batch=args.max_batch,
                             cache_len=args.cache_len,
                             backend=args.backend,
                             kv_mode=args.kv_mode,
                             kv_blocks=args.kv_blocks,
                             block_size=args.block_size,
                             spec_decode=spec,
                             prefill_budget=args.prefill_budget,
                             interleave=not args.no_interleave,
                             admission=args.admission,
                             tracer=tracer,
                             # the launcher is the wall-clock boundary:
                             # live latency numbers want real time
                             # (the engine binds it to the tracer too)
                             clock=time.time)
    t0 = time.time()
    if exposures is not None:
        for key, text in prefix_texts.items():
            engine.register_prefix(key, text)
        for p, exp in zip(prompts, exposures):
            engine.add_request(
                f"{prefix_texts[exp.key_str]}\nTask: {p}",
                max_new_tokens=args.max_new,
                sampler=SamplerConfig(
                    temperature=args.temperature, top_k=40),
                prefix_key=exp.key_str)
    else:
        for p in prompts:
            engine.add_request(p, max_new_tokens=args.max_new,
                               sampler=SamplerConfig(
                                   temperature=args.temperature,
                                   top_k=40))
    done = engine.run_until_done()
    dt = time.time() - t0
    st = engine.throughput_stats()
    print(f"served {len(done)} requests in {dt:.2f}s | "
          f"decode steps {st['decode_steps']} | "
          f"{st['tokens_generated'] / max(dt, 1e-9):.1f} tok/s")
    if exposures is not None:
        print(f"retrieval[k={args.retriever_k}, "
              f"catalog={len(registry.tools)} tools]: "
              f"{len(prefix_texts)} toolset prefixes for "
              f"{len(prompts)} requests | {st['prefix_hits']} prefix "
              f"hits, {st['prefix_tokens_saved']} prefill tokens saved")
    if spec is not None:
        print(f"spec-decode[k={spec.k}]: {st['tokens_per_step']:.2f} "
              f"tokens/target-forward, accept rate "
              f"{st['spec_accept_rate']:.2f} over "
              f"{st['spec_rounds']} rounds")
    print(f"kv[{st['kv_mode']}]: peak {st['kv_bytes_peak'] / 2**20:.1f} "
          f"MiB of {st['kv_bytes_allocated'] / 2**20:.1f} MiB allocated"
          + (f" | {st['preemptions']} preemptions"
             if st["kv_mode"] == "paged" else ""))
    lat = [r.finish_t - r.enqueue_t for r in done]
    ttft = [r.first_token_t - r.enqueue_t for r in done]
    print(f"p50 latency {sorted(lat)[len(lat)//2]*1000:.0f}ms | "
          f"p50 TTFT {sorted(ttft)[len(ttft)//2]*1000:.0f}ms")
    # TTFT split where the engine observes it: enqueue -> admission,
    # admission -> first token
    queue_wait = engine.metrics.histogram("queue_wait_s").percentile(95)
    prefill_wait = engine.metrics.histogram("prefill_wait_s").percentile(50)
    print(f"p95 queue wait {queue_wait*1000:.0f}ms | "
          f"p50 prefill wait {prefill_wait*1000:.0f}ms")
    if tracer is not None:
        dump_chrome_trace(tracer, args.trace_out)
        print(f"trace: {len(tracer.records)} records -> "
              f"{args.trace_out}")
    return {**st, "requests": len(prompts), "finished": len(done),
            "wall_s": dt, "queue_wait_p95_s": queue_wait,
            "prefill_wait_p50_s": prefill_wait,
            "outputs": [r.output for r in sorted(
                done, key=lambda r: r.request_id)]}


if __name__ == "__main__":
    main()
