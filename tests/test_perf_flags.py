"""Tests for the §Perf hillclimb knobs: every flagged code path must be
numerically identical to the baseline path (they only change layout,
sharding, or what gets rematerialized — never semantics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import MoEConfig
from repro.common.perf import FLAGS, PerfFlags, get_flags, set_flags
from repro.models import moe as M
from repro.models.layers import attention


@pytest.fixture(autouse=True)
def _reset_flags():
    yield
    set_flags(PerfFlags())


def _qkv(B=2, Hq=4, Hkv=2, S=256, hd=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, Hq, S, hd), jnp.float32),
            jax.random.normal(ks[1], (B, Hkv, S, hd), jnp.float32),
            jax.random.normal(ks[2], (B, Hkv, S, hd), jnp.float32))


# ------------------------------------------------------- apply_overrides ----

def test_apply_overrides_types():
    f = PerfFlags().apply_overrides(
        "ssm_scan_chunk=128,moe_capacity_factor=1.5,attn_constraint=auto")
    assert f.ssm_scan_chunk == 128
    assert f.moe_capacity_factor == 1.5
    assert f.attn_constraint == "auto"


def test_apply_overrides_empty_is_default():
    assert PerfFlags().apply_overrides("") == PerfFlags()


# ------------------------------------------------------ window-slice attn ----

@pytest.mark.parametrize("window,cap", [(96, 0.0), (64, 30.0), (200, 0.0)])
def test_window_slice_matches_masked(window, cap):
    q, k, v = _qkv(S=256)
    set_flags(PerfFlags(attn_chunk=64, attn_window_slice="off"))
    ref = attention(q, k, v, causal=True, window=window, cap=cap)
    set_flags(PerfFlags(attn_chunk=64, attn_window_slice="on"))
    out = attention(q, k, v, causal=True, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


def test_window_slice_grad_matches():
    q, k, v = _qkv(S=256)
    f = lambda q: attention(q, k, v, causal=True, window=96).sum()
    set_flags(PerfFlags(attn_chunk=64))
    g_ref = jax.grad(f)(q)
    set_flags(PerfFlags(attn_chunk=64, attn_window_slice="on",
                        attn_chunk_remat="on"))
    g_out = jax.grad(f)(q)
    np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_out),
                               rtol=2e-4, atol=2e-4)


def test_chunk_remat_matches():
    q, k, v = _qkv(S=256)
    f = lambda q: attention(q, k, v, causal=True).sum()
    set_flags(PerfFlags(attn_chunk=64))
    ref, g_ref = f(q), jax.grad(f)(q)
    set_flags(PerfFlags(attn_chunk=64, attn_chunk_remat="on"))
    out, g_out = f(q), jax.grad(f)(q)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_out),
                               rtol=2e-4, atol=2e-4)


def test_attn_constraint_noop_without_mesh():
    q, k, v = _qkv(S=128)
    set_flags(PerfFlags(attn_chunk=64))
    ref = attention(q, k, v, causal=True)
    set_flags(PerfFlags(attn_chunk=64, attn_constraint="auto"))
    out = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=1e-6)


# --------------------------------------------------------------- moe pins ----

def _moe_setup(seed=0):
    from repro.common.config import ModelConfig
    cfg = ModelConfig(
        name="t", family="moe", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=2, d_head=16, d_ff=0, vocab_size=64,
        segments=((("moe",), 2),), mlp_act="silu_glu",
        moe=MoEConfig(n_experts=4, top_k=2, d_expert=48,
                      capacity_factor=1.5))
    p = M.moe_init(jax.random.PRNGKey(seed), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, 32),
                          jnp.float32)
    return cfg, p, x


def test_moe_constraint_noop_without_mesh():
    cfg, p, x = _moe_setup()
    set_flags(PerfFlags())
    y0, a0 = M.moe_ffn(p, x, cfg)
    set_flags(PerfFlags(moe_constraint="auto"))
    y1, a1 = M.moe_ffn(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=1e-6)
    np.testing.assert_allclose(float(a0), float(a1), rtol=1e-6)


def test_moe_gather_pin_noop_without_mesh():
    cfg, p, x = _moe_setup()
    set_flags(PerfFlags(moe_dispatch="gather"))
    y0, _ = M.moe_ffn(p, x, cfg)
    set_flags(PerfFlags(moe_dispatch="gather", moe_constraint="auto"))
    y1, _ = M.moe_ffn(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=1e-6)


def test_moe_capacity_override_changes_drops():
    cfg, p, x = _moe_setup()
    set_flags(PerfFlags(moe_capacity_factor=8.0))   # huge: nothing dropped
    y_full, _ = M.moe_ffn(p, x, cfg)
    set_flags(PerfFlags(moe_capacity_factor=0.1))   # tiny: most dropped
    y_tiny, _ = M.moe_ffn(p, x, cfg)
    # with most tokens dropped the output should differ materially
    assert not np.allclose(np.asarray(y_full), np.asarray(y_tiny),
                           rtol=1e-3, atol=1e-4)


def test_capacity_flag_restores_config_default():
    mcfg = MoEConfig(n_experts=4, top_k=2, d_expert=48, capacity_factor=1.5)
    set_flags(PerfFlags())
    c_default = M.capacity(mcfg, 64)
    set_flags(PerfFlags(moe_capacity_factor=1.5))
    assert M.capacity(mcfg, 64) == c_default


# ------------------------------------------------------------ ssm chunks ----

@pytest.mark.parametrize("chunk", [16, 64, 256])
def test_ssm_scan_chunk_invariance(chunk):
    from repro.common.config import ModelConfig, SSMConfig
    from repro.models.ssm import ssm_forward, ssm_init
    cfg = ModelConfig(
        name="t", family="ssm", n_layers=1, d_model=32, n_heads=2,
        n_kv_heads=2, d_head=16, d_ff=64, vocab_size=64,
        segments=((("hymba_w",), 1),),
        ssm=SSMConfig(d_state=8, d_conv=4, expand=1))
    p = ssm_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 32), jnp.float32)
    set_flags(PerfFlags(ssm_scan_chunk=128))
    y_ref, s_ref = ssm_forward(p, x, cfg)
    set_flags(PerfFlags(ssm_scan_chunk=chunk))
    y, s = ssm_forward(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_ref["h"]), np.asarray(s["h"]),
                               rtol=2e-4, atol=2e-4)


# -------------------------------------------------------- parse_strategy ----

def test_parse_strategy(monkeypatch):
    import os
    # importing dryrun appends a 512-device flag for its own command
    # line: keep it out of any later JAX start in this process
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    from repro.launch.dryrun import parse_strategy
    s = parse_strategy("prefill_seq_axis=model,fsdp=False")
    assert s.prefill_seq_axis == "model"
    assert s.fsdp is False
    assert parse_strategy("").fsdp is True
