"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel body on CPU) + hypothesis properties
of the kernels and of the reaction-point oracles."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention

RNG = np.random.RandomState(0)


def _qkv(b, t, s, h, kv, d, dtype):
    q = jnp.asarray(RNG.randn(b, t, h, d), dtype) * 0.3
    k = jnp.asarray(RNG.randn(b, s, kv, d), dtype) * 0.3
    v = jnp.asarray(RNG.randn(b, s, kv, d), dtype) * 0.3
    return q, k, v


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # b, t, h, kv, d, causal, window, softcap, bq, bk
    (1, 128, 4, 2, 64, True, None, 0.0, 64, 64),
    (2, 256, 8, 8, 64, True, None, 50.0, 64, 64),
    (1, 200, 4, 1, 64, True, 64, 0.0, 64, 64),       # ragged + window
    (2, 128, 6, 2, 128, False, None, 0.0, 64, 64),   # encoder
    (1, 512, 4, 2, 64, True, 128, 30.0, 128, 128),
    (1, 96, 2, 2, 32, True, 32, 0.0, 32, 64),
    (1, 80, 4, 4, 64, True, None, 0.0, 64, 64),      # ragged tail block
]


@pytest.mark.parametrize(
    "b,t,h,kv,d,causal,window,cap,bq,bk", FLASH_CASES)
def test_flash_matches_ref_f32(b, t, h, kv, d, causal, window, cap, bq, bk):
    q, k, v = _qkv(b, t, t, h, kv, d, jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=cap, block_q=bq, block_k=bk,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 3e-5)])
def test_flash_dtypes(dtype, tol):
    q, k, v = _qkv(1, 128, 128, 4, 2, 64, dtype)
    out = flash_attention(q, k, v, interpret=True, block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


def test_flash_block_shape_invariance():
    """Result must not depend on the chosen BlockSpec tiling."""
    q, k, v = _qkv(1, 256, 256, 4, 2, 64, jnp.float32)
    outs = [flash_attention(q, k, v, window=96, block_q=bq, block_k=bk,
                            interpret=True)
            for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kv,d,cap,bk", [
    (2, 256, 8, 2, 64, 0.0, 128),
    (1, 1000, 4, 1, 64, 50.0, 256),     # ragged
    (3, 128, 16, 8, 128, 0.0, 64),
    (1, 64, 4, 4, 32, 0.0, 64),
])
def test_decode_matches_ref(b, s, h, kv, d, cap, bk):
    q = jnp.asarray(RNG.randn(b, h, d), jnp.float32) * 0.3
    k = jnp.asarray(RNG.randn(b, s, kv, d), jnp.float32) * 0.3
    v = jnp.asarray(RNG.randn(b, s, kv, d), jnp.float32) * 0.3
    valid = jnp.asarray(RNG.rand(b, s) > 0.3)
    out = decode_attention(q, k, v, valid, softcap=cap, block_k=bk,
                           interpret=True)
    want = ref.decode_attention_ref(q, k, v, valid, softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_decode_ring_mask_single_survivor():
    """Degenerate mask: only one valid slot -> output == its value row."""
    b, s, h, kv, d = 1, 64, 4, 2, 32
    q = jnp.asarray(RNG.randn(b, h, d), jnp.float32)
    k = jnp.asarray(RNG.randn(b, s, kv, d), jnp.float32)
    v = jnp.asarray(RNG.randn(b, s, kv, d), jnp.float32)
    valid = jnp.zeros((b, s), bool).at[0, 17].set(True)
    out = decode_attention(q, k, v, valid, interpret=True, block_k=32)
    want = jnp.repeat(v[0, 17], h // kv, 0).reshape(1, h, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def _rp_params(dt=1e-6):
    return ref.RPParams(g=1 / 256, rate_decrease=0.5, timer_T=55e-6,
                        byte_B=10e6, rai=5e6, rhai=25e6, fr_stages=5,
                        min_rate=1e6, line_rate=12.5e9, dt=dt)


# ---------------------------------------------------------------------------
# hypothesis property tests (system invariants)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(t=st.integers(8, 96), h=st.sampled_from([2, 4]),
       kv=st.sampled_from([1, 2]), window=st.one_of(
           st.none(), st.integers(4, 64)))
def test_flash_rows_are_convex_combinations(t, h, kv, window):
    """softmax(QK)V rows lie inside the convex hull of V rows: the output
    max must never exceed V's max (and min symmetric)."""
    if h % kv:
        h = kv
    q, k, v = _qkv(1, t, t, h, kv, 32, jnp.float32)
    # fresh randomness per example is fine; convexity is shape-independent
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_k=32, interpret=True)
    assert float(out.max()) <= float(v.max()) + 1e-4
    assert float(out.min()) >= float(v.min()) - 1e-4


@settings(max_examples=20, deadline=None)
@given(f=st.integers(1, 300), frac=st.floats(0, 1))
def test_rp_rates_stay_in_bounds(f, frac):
    """RP invariant: rates remain within [min_rate, line_rate] under any
    CNP pattern (no runaway, no starvation)."""
    r = np.random.RandomState(f)
    p = _rp_params()
    st_ = ref.RPState(
        rate=jnp.asarray(r.rand(f) * 12.5e9 + 1e6, jnp.float32),
        target=jnp.asarray(r.rand(f) * 12.5e9 + 1e6, jnp.float32),
        alpha=jnp.asarray(r.rand(f), jnp.float32),
        byte_cnt=jnp.zeros((f,), jnp.float32),
        tmr=jnp.zeros((f,), jnp.float32),
        alpha_tmr=jnp.zeros((f,), jnp.float32),
        bc_stage=jnp.zeros((f,), jnp.float32),
        t_stage=jnp.zeros((f,), jnp.float32))
    for i in range(5):
        cnp = jnp.asarray(r.rand(f) < frac)
        st_ = ref.rp_update_ref(st_, cnp, p)
    assert float(st_.rate.min()) >= p.min_rate - 1
    assert float(st_.rate.max()) <= p.line_rate + 1
    assert np.all(np.isfinite(np.asarray(st_.rate)))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 100))
def test_erp_cnp_sets_rate_to_fair_share(seed):
    """ERP invariant: a CNP pins the rate to settle*target immediately."""
    r = np.random.RandomState(seed)
    F = 64
    p = ref.ERPParams(settle=0.98, hold=50e-6, min_rate=1e6,
                      line_rate=12.5e9, dt=1e-6)
    rate = jnp.asarray(r.rand(F) * 12.5e9, jnp.float32)
    tgt = jnp.asarray(r.rand(F) * 12.5e9 + 2e6, jnp.float32)
    cnp = jnp.ones((F,), bool)
    new_rate, _ = ref.erp_update_ref(
        rate, jnp.zeros((F,)), cnp, tgt, jnp.full((F,), 5e12), p)
    np.testing.assert_allclose(
        np.asarray(new_rate),
        np.clip(0.98 * np.asarray(tgt), 1e6, 12.5e9), rtol=1e-6)
