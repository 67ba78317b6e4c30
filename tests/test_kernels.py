"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel body on CPU) + hypothesis properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.cc_step import erp_step, gen_np_step, rp_step
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


# ---------------------------------------------------------------------------
# cc_step (the paper's RP/ERP at scale)
# ---------------------------------------------------------------------------

def _rp_params(dt=1e-6):
    return ref.RPParams(g=1 / 256, rate_decrease=0.5, timer_T=55e-6,
                        byte_B=10e6, rai=5e6, rhai=25e6, fr_stages=5,
                        min_rate=1e6, line_rate=12.5e9, dt=dt)


# F values straddle every _pad_to_grid boundary: sub-lane (1, 5, 127),
# one-over-lane (129, 130), exactly one grid block (8192), one-over-block
# (8193), and multi-block ragged (100_001).
@pytest.mark.parametrize("F", [1, 5, 127, 129, 130, 8192, 8193, 100_001])
def test_rp_kernel_matches_ref(F):
    r = np.random.RandomState(F)
    st = ref.RPState(
        rate=jnp.asarray(r.rand(F) * 12.5e9, jnp.float32),
        target=jnp.asarray(r.rand(F) * 12.5e9, jnp.float32),
        alpha=jnp.asarray(r.rand(F), jnp.float32),
        byte_cnt=jnp.asarray(r.rand(F) * 10e6, jnp.float32),
        tmr=jnp.asarray(r.rand(F) * 55e-6, jnp.float32),
        alpha_tmr=jnp.asarray(r.rand(F) * 55e-6, jnp.float32),
        bc_stage=jnp.asarray(r.randint(0, 8, F), jnp.float32),
        t_stage=jnp.asarray(r.randint(0, 8, F), jnp.float32))
    cnp = jnp.asarray(r.rand(F) > 0.6)
    out = rp_step(st, cnp, _rp_params(), interpret=True)
    want = ref.rp_update_ref(st, cnp, _rp_params())
    for a, b, name in zip(out, want, ref.RPState._fields):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   err_msg=f"F={F} {name}")


@pytest.mark.parametrize("F", [1, 127, 129, 8193, 50_000])
def test_erp_kernel_matches_ref(F):
    r = np.random.RandomState(7)
    p = ref.ERPParams(settle=0.98, hold=50e-6, min_rate=1e6,
                      line_rate=12.5e9, dt=1e-6)
    args = (jnp.asarray(r.rand(F) * 12.5e9, jnp.float32),
            jnp.asarray(r.rand(F) * 1e-4, jnp.float32),
            jnp.asarray(r.rand(F) > 0.5),
            jnp.asarray(r.rand(F) * 12.5e9, jnp.float32),
            jnp.full((F,), 5e12, jnp.float32))
    r1, h1 = erp_step(*args, p, interpret=True)
    r2, h2 = ref.erp_update_ref(*args, p)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-6)


@pytest.mark.parametrize("F", [1, 127, 129, 8193, 50_000])
def test_swift_kernel_matches_ref(F):
    """Delay-target reaction kernel vs its jnp oracle (exact f32 —
    the fluid step's swift stage routes through this behind
    use_kernels, so drift here is drift in the sweep)."""
    from repro.kernels.cc_step import swift_step
    r = np.random.RandomState(11)
    p = ref.SwiftKParams(target=3e-6, beta=0.8, ai=1e12, guard=25e-6,
                         min_rate=1e6, line_rate=12.5e9, dt=1e-6)
    rate = jnp.asarray(r.rand(F) * 12.5e9, jnp.float32)
    cool = jnp.asarray(np.where(r.rand(F) > 0.5, r.rand(F) * 5e-5, 0.0),
                       jnp.float32)
    qd = jnp.asarray(np.where(r.rand(F) > 0.3, r.rand(F) * 2e-5, 0.0),
                     jnp.float32)
    r1, c1 = swift_step(rate, cool, qd, p, interpret=True)
    r2, c2 = ref.swift_update_ref(
        rate, cool, qd, target=p.target, beta=p.beta, ai=p.ai,
        guard=p.guard, min_rate=p.min_rate, line_rate=p.line_rate,
        dt=p.dt)
    assert np.array_equal(np.asarray(r1), np.asarray(r2)), F
    assert np.array_equal(np.asarray(c1), np.asarray(c2)), F


@pytest.mark.parametrize("F", [1, 127, 129, 8193])
def test_gen_np_kernel_matches_jnp(F):
    """Fused generation + notification-timer kernel vs the fluid step's
    phase-1/5a arithmetic (exact, incl. inf volumes / buffers)."""
    r = np.random.RandomState(F)
    nicq = jnp.asarray(r.rand(F) * 1e6, jnp.float32)
    offered = jnp.asarray(r.rand(F) * 1e7, jnp.float32)
    dropped = jnp.asarray(r.rand(F) * 1e5, jnp.float32)
    np_tmr = jnp.asarray(r.rand(F) * 1e-4, jnp.float32)
    gen_rate = jnp.asarray(r.rand(F) * 12.5e9, jnp.float32)
    t_start = jnp.asarray(r.rand(F) * 2e-3, jnp.float32)
    t_stop = jnp.asarray(
        np.where(r.rand(F) > 0.5, r.rand(F) * 3e-3, np.inf), jnp.float32)
    volume = jnp.asarray(
        np.where(r.rand(F) > 0.5, r.rand(F) * 2e7, np.inf), jnp.float32)
    nic_buffer = jnp.asarray(
        np.where(r.rand(F) > 0.3, 4e6, np.inf), jnp.float32)
    t_sec, dt = jnp.float32(1.2e-3), jnp.float32(1e-6)
    got = gen_np_step(nicq, offered, dropped, np_tmr, gen_rate, t_start,
                      t_stop, volume, nic_buffer, t_sec=t_sec, dt=dt,
                      interpret=True)
    active = (t_sec >= t_start) & (t_sec < t_stop)
    gen = jnp.where(active, gen_rate, 0.0) * dt
    gen = jnp.minimum(gen, jnp.maximum(volume - offered, 0.0))
    q = nicq + gen
    over = jnp.maximum(q - nic_buffer, 0.0)
    want = (q - over, offered + gen - over, dropped + over, np_tmr + dt)
    for g, w, name in zip(got, want,
                          ("nicq", "offered", "dropped", "np_tmr")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (F, name)


def test_cc_kernels_accept_traced_params():
    """CC constants are SMEM data, not compile-time floats: jitting over
    traced params must work and vary the result without recompiling."""
    F = 300
    r = np.random.RandomState(3)
    rate = jnp.asarray(r.rand(F) * 12.5e9, jnp.float32)
    hold = jnp.zeros((F,), jnp.float32)
    cnp = jnp.asarray(r.rand(F) > 0.5)
    tgt = jnp.asarray(r.rand(F) * 12.5e9, jnp.float32)
    slope = jnp.full((F,), 5e12, jnp.float32)

    calls = []

    @jax.jit
    def f(settle):
        calls.append(None)       # traces once per shape, not per value
        p = ref.ERPParams(settle=settle, hold=jnp.float32(50e-6),
                          min_rate=jnp.float32(1e6),
                          line_rate=jnp.float32(12.5e9),
                          dt=jnp.float32(1e-6))
        return erp_step(rate, hold, cnp, tgt, slope, p, interpret=True)

    r1, _ = f(jnp.float32(0.98))
    r2, _ = f(jnp.float32(0.50))
    assert len(calls) == 1
    assert not np.array_equal(np.asarray(r1), np.asarray(r2))
    want, _ = ref.erp_update_ref(
        rate, hold, cnp, tgt, slope,
        ref.ERPParams(0.5, 50e-6, 1e6, 12.5e9, 1e-6))
    np.testing.assert_allclose(np.asarray(r2), np.asarray(want), rtol=1e-6)


# ---------------------------------------------------------------------------
# fluid_step megakernel: whole-step parity off the tile grid + under vmap
# ---------------------------------------------------------------------------

def _mega_scn(F):
    """F same-shaped flows on the legacy CLOS — F straddles the lane /
    block boundaries the per-flow kernels pad to (1, 127, 129, 8193),
    so the megakernel's lifted (1, F) layouts see ragged shapes."""
    from repro.core import PAPER_CONFIG, ScenarioSpec
    pairs = [(i % 16, 16 + (i * 5) % 16) for i in range(F)]
    spec = ScenarioSpec.flows(pairs, t_start=0.0, t_stop=0.5e-3,
                              label=f"mega{F}")
    return spec.build(PAPER_CONFIG), PAPER_CONFIG


def _assert_states_equal(fa, fb, ctx):
    la = jax.tree_util.tree_flatten_with_path(fa)[0]
    lb = jax.tree_util.tree_flatten_with_path(fb)[0]
    assert len(la) == len(lb)
    for (pa, ga), (pb, gb) in zip(la, lb):
        assert pa == pb
        assert np.array_equal(np.asarray(ga), np.asarray(gb)), \
            (ctx, jax.tree_util.keystr(pa))


@pytest.mark.parametrize("F", [1, 127, 129, 8193])
def test_megakernel_matches_scat_off_tile_grid(F):
    """Whole-step megakernel vs the scatter engine at non-tile-aligned
    flow counts: exact equality of state and step trace after a short
    jitted run (mirrors the rp/erp ragged-shape sweeps above, but for
    the fused whole-step kernel)."""
    from repro.core.fluid import init_state, make_step_fn
    scn, cfg = _mega_scn(F)
    n = 5 if F > 1000 else 20
    finals, traces = [], []
    for kw in (dict(reduce="scat"),
               dict(use_kernels="mega", interpret=True)):
        step = jax.jit(make_step_fn(scn, cfg, **kw))
        st = init_state(scn, cfg)
        for _ in range(n):
            st, tr = step(st)
        finals.append(st)
        traces.append(tr)
    _assert_states_equal(finals[0], finals[1], f"mega-F{F}-final")
    _assert_states_equal(traces[0], traces[1], f"mega-F{F}-trace")


def test_megakernel_under_vmap_on_sweep_run_axis():
    """vmap over the Sweep run axis must batch straight through the
    megakernel's pallas_call: a 3-point sweep (mixed schemes) through
    ``use_kernels="mega"`` equals the scatter engine bit for bit."""
    from repro.core import CCScheme, PAPER_CONFIG, ScenarioSpec, Sweep
    spec = ScenarioSpec.paper_incast(roll=0, t_start=0.1e-3,
                                     t_stop=1.2e-3)
    sweep = Sweep.grid(
        {s.name: PAPER_CONFIG.replace(scheme=s) for s in CCScheme},
        {"inc": spec})
    ra = sweep.run(n_steps=60, trace_every=10, reduce="scat")
    rb = sweep.run(n_steps=60, trace_every=10, use_kernels="mega",
                   interpret=True)
    _assert_states_equal(ra.traces, rb.traces, "mega-vmap-traces")
    _assert_states_equal(ra.final, rb.final, "mega-vmap-final")


def test_megakernel_vmem_guard_refuses_oversized_state():
    """Off interpret mode the launcher enforces the VMEM budget: a
    state+scenario footprint beyond ~14 MiB must be refused with the
    block-size pointer, not handed to the compiler."""
    from repro.kernels.fluid_step import (MEGA_VMEM_CAP, mega_footprint,
                                          megastep)
    from repro.core.fluid import scenario_device, step_body_fn, \
        init_state, step_params
    scn, cfg = _mega_scn(127)
    st = init_state(scn, cfg)
    sd = scenario_device(scn)
    assert 0 < mega_footprint(st, sd) < MEGA_VMEM_CAP
    big = st._replace(
        qh=jnp.zeros((MEGA_VMEM_CAP // 8 + 1, 2), jnp.float32))
    body = step_body_fn(dt=float(cfg.sim.dt),
                        n_switches=int(scn.n_switches))
    with pytest.raises(ValueError, match="VMEM"):
        megastep(big, sd, step_params(cfg), body=body, interpret=False)


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
