"""Compile the main-path Pallas kernels and a full-width decode step for
a described (not attached) TPU v5e chip.

Nothing runs: the TPU compiler refuses here what it would refuse on the
chip (blocks off the (8, 128) tiling, scalar VMEM stores, unsupported
broadcasts), at planner-proxy-100m widths (Hq=12, Hkv=4, hd=64, cache
4096, batch 4). The topology is described inside a fixture, so only the
worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import backend as KB
from repro.kernels.flash_decode import flash_decode
from repro.kernels.flash_decode_paged import flash_decode_paged
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.flash_verify import flash_verify, flash_verify_paged
from repro.kernels.mlstm_scan import mlstm_scan
from repro.kernels.ssm_scan import ssm_scan
from repro.models.model import decode_step, init_cache, init_params

HQ, HKV, HD, B, SC, BS, W = 12, 4, 64, 4, 4096, 16, 5
NB = B * SC // BS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    """Lower and compile for the described chip; the HLO text."""
    lowered = jax.jit(fn).lower(*args)
    lowered.compile()
    return lowered.as_text()


@pytest.mark.parametrize("sq", [130, 300, 1024, 2560])
def test_flash_prefill_compiles(one_chip, sq):
    q = _shape(one_chip, (1, HQ, sq, HD))
    kv = _shape(one_chip, (1, HKV, sq, HD))
    hlo = _compile(lambda q, k, v: flash_prefill(q, k, v, interpret=False),
                   q, kv, kv)
    assert "tpu_custom_call" in hlo


def test_flash_prefill_extend_compiles(one_chip):
    q = _shape(one_chip, (1, HQ, 300, HD))
    kv = _shape(one_chip, (1, HKV, SC, HD))
    off = _shape(one_chip, (), jnp.int32)
    _compile(lambda q, k, v, o: flash_prefill(q, k, v, q_offset=o,
                                              interpret=False),
             q, kv, kv, off)


def test_decode_kernels_compile(one_chip):
    kvl = _shape(one_chip, (B,), jnp.int32)
    dense = _shape(one_chip, (B, HKV, SC, HD))
    pages = _shape(one_chip, (NB, HKV, BS, HD))
    tab = _shape(one_chip, (B, SC // BS), jnp.int32)
    q1 = _shape(one_chip, (B, HQ, HD))
    qw = _shape(one_chip, (B, HQ, W, HD))
    _compile(lambda q, k, v, l: flash_decode(q, k, v, l, interpret=False),
             q1, dense, dense, kvl)
    _compile(lambda q, k, v, t, l: flash_decode_paged(
        q, k, v, t, l, interpret=False), q1, pages, pages, tab, kvl)
    _compile(lambda q, k, v, l: flash_verify(q, k, v, l, interpret=False),
             qw, dense, dense, kvl)
    _compile(lambda q, k, v, t, l: flash_verify_paged(
        q, k, v, t, l, interpret=False), qw, pages, pages, tab, kvl)


def test_ssm_scan_compiles_at_hymba_width(one_chip):
    cfg = get_config("hymba-1.5b")
    di, n, s = cfg.d_model * cfg.ssm.expand, cfg.ssm.d_state, 130
    f32 = jnp.float32
    seq = _shape(one_chip, (1, s, di), f32)
    bc = _shape(one_chip, (1, s, n), f32)
    _compile(lambda dt, x, b, c, a, h: ssm_scan(dt, x, b, c, a, h,
                                                interpret=False),
             seq, seq, bc, bc, _shape(one_chip, (di, n), f32),
             _shape(one_chip, (1, di, n), f32))


def test_mlstm_scan_compiles_at_xlstm_width(one_chip):
    cfg = get_config("xlstm-125m")
    H = cfg.n_heads
    hd = int(cfg.d_model * cfg.xlstm.proj_factor) // H
    f32 = jnp.float32
    seq = _shape(one_chip, (1, H, 130, hd), f32)
    gate = _shape(one_chip, (1, H, 130), f32)
    state = (_shape(one_chip, (1, H, hd, hd), f32),
             _shape(one_chip, (1, H, hd), f32),
             _shape(one_chip, (1, H), f32))
    _compile(lambda q, k, v, i, f, st: mlstm_scan(q, k, v, i, f, st,
                                                  interpret=False),
             seq, seq, seq, gate, gate, state)


def test_full_width_decode_step_compiles(one_chip, monkeypatch):
    # the backend picks interpret mode from jax.default_backend(), which
    # is the CPU here; compile the kernels as the chip would
    monkeypatch.setattr(KB, "_interpret", lambda: False)
    cfg = get_config("planner-proxy-100m")
    on_chip = lambda t: jax.tree.map(
        lambda s: _shape(one_chip, s.shape, s.dtype), t)
    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: {
        **init_cache(cfg, B, SC), "pos": jnp.zeros((B,), jnp.int32)}))
    tokens = _shape(one_chip, (B, 1), jnp.int32)
    hlo = _compile(lambda p, c, t: decode_step(p, cfg, c, {"tokens": t},
                                               backend="pallas"),
                   params, cache, tokens)
    assert "tpu_custom_call" in hlo


def test_engine_programs_name_their_kernels(one_chip, monkeypatch):
    """The engine's jitted steps lower as ``jit_engine_<step>`` modules
    whose Pallas calls carry the kernel's own name, the names the
    profiler's module and operation lines show on the chip."""
    from repro.serving.engine import InferenceEngine
    monkeypatch.setattr(KB, "_interpret", lambda: False)
    cfg = get_config("planner-proxy-100m")
    on_chip = lambda t: jax.tree.map(
        lambda s: _shape(one_chip, s.shape, s.dtype), t)
    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    eng = InferenceEngine(cfg, params, max_batch=2, cache_len=256,
                          backend="pallas")
    cache = on_chip(jax.eval_shape(lambda: eng.cache))
    decode = eng._decode.lower(
        params, cache, {"tokens": _shape(one_chip, (2, 1), jnp.int32)})
    prefill = eng._prefill.lower(
        params, {"tokens": _shape(one_chip, (1, 256), jnp.int32)})
    assert decode.as_text().startswith("module @jit_engine_decode")
    assert prefill.as_text().startswith("module @jit_engine_prefill")
    assert "flash_decode" in decode.as_text()
    assert "flash_prefill" in prefill.as_text()
