"""Per-kernel interpret-mode validation: shape/dtype sweeps vs the pure-jnp
oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_decode import flash_decode
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.moe_router import moe_router_topk
from repro.kernels.mlstm_scan import mlstm_scan
from repro.kernels.ref import (attention_ref, decode_attention_ref,
                               mlstm_ref, mlstm_scan_ref, router_topk_ref,
                               selective_scan_ref)
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.tiling import fit_block

RNG = np.random.default_rng(0)


def _rand(shape, dtype=np.float32):
    return jnp.asarray(RNG.standard_normal(shape, dtype=np.float32)
                       .astype(dtype))


@pytest.mark.parametrize("B,Hq,Hkv,S,hd", [
    (2, 4, 2, 256, 64), (1, 8, 8, 512, 128), (2, 2, 1, 128, 32),
    (1, 6, 3, 384, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_sweep(B, Hq, Hkv, S, hd, dtype):
    q, k, v = (jnp.asarray(_rand((b, h, S, hd)), dtype)
               for b, h in ((B, Hq), (B, Hkv), (B, Hkv)))
    out = flash_prefill(q, k, v, causal=True, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("window,cap,causal", [
    (64, 0.0, True), (0, 50.0, True), (128, 30.0, True), (0, 0.0, False),
])
def test_flash_prefill_variants(window, cap, causal):
    q = _rand((2, 4, 256, 64))
    k = _rand((2, 2, 256, 64))
    v = _rand((2, 2, 256, 64))
    out = flash_prefill(q, k, v, causal=causal, window=window, cap=cap,
                        interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window, cap=cap)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-3


@pytest.mark.parametrize("n,max_block,align,want", [
    (130, 128, 8, (72, 144)), (300, 128, 8, (104, 312)),
    (100, 128, 8, (100, 100)), (1024, 128, 8, (128, 1024)),
    (1600, 256, 128, (256, 1792)), (384, 256, 8, (192, 384)),
])
def test_fit_block(n, max_block, align, want):
    block, padded = fit_block(n, max_block, align)
    assert (block, padded) == want
    assert padded >= n and padded % block == 0
    assert block == n or (block % align == 0 and block <= max_block)


@pytest.mark.parametrize("Sq,Sk,q_offset,causal,window", [
    (130, 130, 0, True, 0), (300, 300, 0, True, 0),
    (300, 300, 0, False, 0),         # padded keys masked without causal
    (300, 300, 0, True, 64), (7, 7, 0, True, 0),
    (37, 300, 70, True, 0),          # extend at an offset of a cache
    (300, 1001, 0, False, 0),
])
def test_flash_prefill_any_length(Sq, Sk, q_offset, causal, window):
    """Lengths that are no multiple of the tiling: padded to whole
    8-aligned blocks, pad keys masked, pad query rows sliced off."""
    q = _rand((1, 4, Sq, 64))
    k = _rand((1, 2, Sk, 64))
    v = _rand((1, 2, Sk, 64))
    out = flash_prefill(q, k, v, causal=causal, window=window,
                        q_offset=q_offset, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window,
                        q_offset=q_offset)
    assert out.shape == ref.shape
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-3


@pytest.mark.parametrize("B,Hq,Hkv,S,hd,kvl", [
    (2, 4, 2, 1024, 64, 700), (1, 8, 1, 512, 128, 512),
    (3, 4, 4, 2048, 64, 1), (2, 8, 2, 512, 64, 511),
])
def test_flash_decode_sweep(B, Hq, Hkv, S, hd, kvl):
    q = _rand((B, Hq, hd))
    k = _rand((B, Hkv, S, hd))
    v = _rand((B, Hkv, S, hd))
    out = flash_decode(q, k, v, kvl, interpret=True)
    ref = decode_attention_ref(q, k, v, kvl)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-3


@pytest.mark.parametrize("T,E,k", [(512, 64, 2), (256, 128, 8),
                                   (256, 16, 1), (1024, 384, 8),
                                   (130, 64, 2)])
def test_moe_router_sweep(T, E, k):
    logits = _rand((T, E)) * 3.0
    w, i = moe_router_topk(logits, k, interpret=True)
    wr, ir, _ = router_topk_ref(logits, k)
    assert jnp.allclose(w, wr, atol=1e-5)
    assert jnp.array_equal(i, ir)


@pytest.mark.parametrize("B,S,di,n", [(2, 256, 128, 16), (1, 512, 256, 8),
                                      (2, 128, 512, 16),
                                      (1, 300, 400, 16)])   # both padded
def test_ssm_scan_sweep(B, S, di, n):
    dt = jnp.abs(_rand((B, S, di))) * 0.1
    x = _rand((B, S, di))
    B_ = _rand((B, S, n))
    C_ = _rand((B, S, n))
    A = -jnp.exp(_rand((di, n)))
    h0 = _rand((B, di, n))
    y, h_last = ssm_scan(dt, x, B_, C_, A, h0, interpret=True)
    yr, hr = selective_scan_ref(dt, x, B_, C_, A, h0)
    assert float(jnp.max(jnp.abs(y - yr))) < 1e-3
    assert float(jnp.max(jnp.abs(h_last - hr))) < 1e-3


def test_chunked_mlstm_matches_sequential_oracle():
    """The chunkwise-parallel mLSTM must equal the stabilized sequential
    recurrence from the paper."""
    from repro.common.config import ModelConfig, XLSTMConfig
    from repro.models.xlstm import mlstm_seq, mlstm_state_init

    cfg = ModelConfig(name="t", family="ssm", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=64,
                      segments=((("mlstm",), 1),),
                      xlstm=XLSTMConfig(chunk_size=16, proj_factor=2.0))
    B, S, dh = 2, 80, 64   # S deliberately not a multiple of chunk
    H, hd = 4, 16
    x = _rand((B, S, dh))
    p = {
        "wq": _rand((dh, dh)) * 0.3, "wk": _rand((dh, dh)) * 0.3,
        "wv": _rand((dh, dh)) * 0.3,
        "w_if": _rand((dh, 2 * H)) * 0.3,
        "b_i": jnp.zeros((H,)), "b_f": jnp.full((H,), 3.0),
    }
    y, _ = mlstm_seq(p, x, cfg, mlstm_state_init(cfg, B))
    # oracle on the same projected q/k/v
    to_heads = lambda t: t.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    q = to_heads(x @ p["wq"]).astype(jnp.float32)
    k = to_heads(x @ p["wk"]).astype(jnp.float32)
    v = to_heads(x @ p["wv"]).astype(jnp.float32)
    gif = (x @ p["w_if"]).astype(jnp.float32).reshape(B, S, 2, H)
    i_pre = gif[:, :, 0].transpose(0, 2, 1) + p["b_i"][None, :, None]
    f_pre = gif[:, :, 1].transpose(0, 2, 1) + p["b_f"][None, :, None]
    href = mlstm_ref(q, k, v, i_pre, f_pre)
    yref = href.transpose(0, 2, 1, 3).reshape(B, S, dh)
    # chunked vs sequential differ only in fp32 accumulation order
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - yref))) < 2e-2


@pytest.mark.parametrize("S", [40, 300])    # one whole block; padded pair
def test_mlstm_scan_matches_oracle_with_state(S):
    """The kernel from a carried state equals the sequential oracle, in
    outputs and in the state it hands on; pad steps leave it untouched."""
    B, H, hd = 1, 2, 32
    q, k, v = (_rand((B, H, S, hd)) for _ in range(3))
    i_pre, f_pre = _rand((B, H, S)), _rand((B, H, S)) + 2.0
    _, state = mlstm_scan_ref(q, k, v, i_pre, f_pre)       # warm state
    h, (C, n, m) = mlstm_scan(q, k, v, i_pre, f_pre, state,
                              interpret=True)
    hr, (Cr, nr, mr) = mlstm_scan_ref(q, k, v, i_pre, f_pre, state)
    for got, want in ((h, hr), (C, Cr), (n, nr), (m, mr)):
        assert float(jnp.max(jnp.abs(got - want))) < 2e-3
