"""Kernel backend subsystem: registry semantics, pipeline-level parity
(pallas backend must serve the exact tokens of the reference backend),
and kernel-vs-ref sweeps at the serving shapes InferenceEngine uses."""
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
from repro.kernels import backend as KB
from repro.kernels.ref import attention_ref, decode_attention_ref
from repro.models.model import decode_step, init_params, prefill
from repro.serving.engine import InferenceEngine
from repro.serving.pipeline import GeckOptPipeline, PipelineConfig
from repro.serving.sampling import SamplerConfig

RNG = np.random.default_rng(7)


def _rand(shape):
    return jnp.asarray(RNG.standard_normal(shape, dtype=np.float32))


@pytest.fixture(scope="module")
def planner():
    cfg = get_smoke_config("planner-proxy-100m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ------------------------------------------------------------ registry ----

def test_backend_registry_resolution():
    assert set(KB.available_backends()) >= {"reference", "pallas"}
    assert KB.get_backend().name == "reference"          # PerfFlags default
    assert KB.get_backend("pallas").name == "pallas"
    be = KB.get_backend("pallas")
    assert KB.get_backend(be) is be                      # pass-through
    with KB.use_backend("pallas"):
        assert KB.get_backend().name == "pallas"
        assert KB.get_backend("reference").name == "reference"  # arg wins
    assert KB.get_backend().name == "reference"
    with pytest.raises(ValueError):
        KB.get_backend("cuda")


@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, platform, interpret):
    """Kernels compile on a TPU, interpret on the CPU, and refuse any
    other platform instead of quietly interpreting there."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match="not on 'gpu'"):
            KB._interpret()
    else:
        assert KB._interpret() is interpret


# ------------------------------------- serving-shape kernel-vs-ref sweep ----

def test_kernel_vs_ref_at_serving_shapes():
    """flash kernels vs oracles at the bucket shapes the engine actually
    runs: GQA prefill at prompt lengths, chunked-prefill extend at a
    traced q_offset, continuous-batching decode with per-slot (B,) fill
    levels."""
    be = KB.get_backend("pallas")
    Hq, Hkv, hd = 4, 2, 64                       # planner-proxy smoke geometry

    # prefill buckets (engine prefills B=1 prompts)
    for S in (32, 96):
        q, k, v = _rand((1, Hq, S, hd)), _rand((1, Hkv, S, hd)), \
            _rand((1, Hkv, S, hd))
        out = be.attention(q, k, v, causal=True)
        ref = attention_ref(q, k, v, causal=True)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-3

    # chunked-prefill extend: S new tokens at offset `pos` of a filled cache
    Sc, pos, S = 128, 70, 16
    k, v = _rand((1, Hkv, Sc, hd)), _rand((1, Hkv, Sc, hd))
    q = _rand((1, Hq, S, hd))
    out = be.attention(q, k, v, causal=True, q_offset=jnp.asarray(pos))
    ref = attention_ref(q, k, v, causal=True, q_offset=pos)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-3

    # continuous-batching decode: every slot at its own fill level
    for Sc in (96, 128, 512):
        B = 3
        q1 = _rand((B, Hq, hd))
        k, v = _rand((B, Hkv, Sc, hd)), _rand((B, Hkv, Sc, hd))
        kvl = jnp.asarray([Sc, Sc // 2, 1], jnp.int32)
        out = be.decode_attention(q1, k, v, kvl)
        ref = decode_attention_ref(q1, k, v, kvl)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-3


# --------------------------------------------------- engine-level parity ----

def test_engine_parity_continuous_batching(planner):
    """The pallas backend must emit the exact token ids of the reference
    backend through the full engine loop — prefix cache, chunked-prefill
    extends, staggered continuous-batching decode — at the same seed."""
    cfg, params = planner

    def serve(backend):
        eng = InferenceEngine(cfg, params, max_batch=3, cache_len=128,
                              seed=0, backend=backend)
        eng.register_prefix("gate", "classify the user intent:")
        rids = [eng.add_request(
            f"classify the user intent: query number {i}",
            max_new_tokens=5, sampler=SamplerConfig(temperature=0.0),
            prefix_key="gate") for i in range(5)]   # 5 requests, 3 slots
        done = {r.request_id: r.output for r in eng.run_until_done()}
        return [done[r] for r in rids], eng.throughput_stats()

    ref_out, ref_stats = serve("reference")
    pl_out, pl_stats = serve("pallas")
    assert ref_out == pl_out
    assert ref_stats == pl_stats


def test_engine_parity_across_architectures():
    """Greedy prefill+decode token parity reference vs pallas for every
    kernel consumer: MoE routing, SSM scan, mLSTM scan, sliding-window
    attention."""
    for arch in ("arctic-480b", "hymba-1.5b", "xlstm-125m", "gemma2-2b"):
        cfg = get_smoke_config(arch)
        params = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                  cfg.vocab_size)
        seqs = {}
        for be in ("reference", "pallas"):
            logits, cache = prefill(params, cfg, {"tokens": toks},
                                    cache_len=64, backend=be)
            cache["pos"] = jnp.asarray([24, 24], jnp.int32)
            out = [np.asarray(jnp.argmax(logits, -1))]
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            for _ in range(3):
                logits, cache = decode_step(params, cfg, cache,
                                            {"tokens": tok}, backend=be)
                tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
                out.append(np.asarray(tok[:, 0]))
            seqs[be] = np.stack(out)
        assert (seqs["reference"] == seqs["pallas"]).all(), arch


# ------------------------------------------------- pipeline-level parity ----

def test_pipeline_parity_reference_vs_pallas(planner):
    """End-to-end: the concurrent gate→plan→execute pipeline with engine
    mirroring must produce identical task metrics AND identical engine
    turn tokens under both backends at the same seed."""
    cfg, params = planner
    world = build_world(0)
    tasks = make_benchmark(world, 4)
    intent_map = build_intent_map(tasks, DEFAULT_REGISTRY)

    def run(backend):
        engine = InferenceEngine(cfg, params, max_batch=2, cache_len=4096,
                                 seed=0, backend=backend)
        gate = IntentGate(intent_map, ScriptedIntentClassifier(
            1.0, np.random.default_rng(0)), DEFAULT_REGISTRY.libraries())
        agent = Agent(DEFAULT_REGISTRY, world,
                      PlannerConfig(mode="cot", few_shot=False), gate=gate,
                      seed=0)
        pipe = GeckOptPipeline(
            agent, PipelineConfig(max_concurrent=4, engine_max_new_tokens=2),
            engine=engine)
        results = pipe.run(tasks)
        turns = [r.output for es in pipe._engine_sessions for r in es.turns]
        metrics = [(r.completed_plan, r.steps, r.ledger.total_tokens)
                   for r in results]
        return metrics, turns, pipe.stats.summary()

    m_ref, t_ref, s_ref = run("reference")
    m_pl, t_pl, s_pl = run("pallas")
    assert m_ref == m_pl
    assert t_ref == t_pl and len(t_ref) == 4
    assert s_ref["engine_backend"] == "reference"
    assert s_pl["engine_backend"] == "pallas"
