"""Sweep/ScenarioSpec API tests: batched == individual, padding
invariance, trace decimation, delay-line sizing, vectorised metrics."""

import numpy as np
import pytest

from repro.core import (CCConfig, CCScheme, PAPER_CONFIG, ScenarioSpec,
                        Sweep, config_grid, delay_depth, dense_reduce_rows,
                        init_state, make_step_fn, pad_scenario,
                        paper_incast, run)

CFG = PAPER_CONFIG
N_STEPS = 3000


@pytest.fixture(scope="module")
def sweep_vs_individual():
    spec = ScenarioSpec.paper_incast(roll=0)
    sweep = Sweep.grid(
        configs={s.name: CFG.replace(scheme=s) for s in CCScheme},
        scenarios={"hol": spec})
    batched = sweep.run(n_steps=N_STEPS)
    single = {s: run(spec.build(CFG.replace(scheme=s)),
                     CFG.replace(scheme=s), n_steps=N_STEPS)
              for s in CCScheme}
    return batched, single


# ---------------------------------------------------------------------------
# one-jit sweep == per-point run()
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", list(CCScheme))
def test_sweep_matches_individual_runs(sweep_vs_individual, scheme):
    """The batched vmap-of-scan reproduces run() bit-for-bit: traces
    AND final state."""
    batched, single = sweep_vs_individual
    rs = batched[f"{scheme.name}/hol"]
    ri = single[scheme]
    for field in ("delivered", "rate", "inst_thr", "max_q", "marked",
                  "cnp"):
        np.testing.assert_array_equal(
            getattr(rs, field), getattr(ri, field), err_msg=field)
    np.testing.assert_array_equal(np.asarray(rs.final.qh),
                                  np.asarray(ri.final.qh))
    np.testing.assert_array_equal(np.asarray(rs.final.rate),
                                  np.asarray(ri.final.rate))


def test_sweep_point_views(sweep_vs_individual):
    batched, _ = sweep_vs_individual
    assert len(batched) == 3
    assert "DCQCN/hol" in batched
    assert batched.names == [f"{s.name}/hol" for s in CCScheme]
    # index and name access agree
    np.testing.assert_array_equal(batched[0].delivered,
                                  batched["PFC_ONLY/hol"].delivered)


def test_sweep_mixed_scenario_shapes():
    """Scenarios of different F stack via padding and still run."""
    res = Sweep.grid(
        configs=CFG,
        scenarios={"i2": ScenarioSpec.incast(2, victim=False),
                   "i8": ScenarioSpec.incast(8, victim=False)}
    ).run(n_steps=1000)
    assert res["i2"].delivered.shape[1] == 2
    assert res["i8"].delivered.shape[1] == 8


def test_config_grid_paths():
    grid = config_grid(CFG, **{"dcqcn.kmin": [8192.0, 15360.0]})
    assert len(grid) == 2
    assert grid["kmin=8192"].dcqcn.kmin == 8192.0
    assert grid["kmin=8192"].rev == CFG.rev          # untouched subtree


# ---------------------------------------------------------------------------
# padding invariance
# ---------------------------------------------------------------------------

def _run_on(engine: str, scn, n_steps: int):
    """One point through ``run`` (the jagged layout it derives), or as a
    one-point ``Sweep`` on the segment sum (``dense_rows=0``, what the
    what-if service pads its power-of-two flow buckets on) or on the
    rectangle pinned to the scenario's longest list."""
    if engine == "jagged":
        return run(scn, CFG, n_steps=n_steps)
    rows = 0 if engine == "segment_sum" else dense_reduce_rows(scn)
    assert rows >= 0 and (rows > 0) == (engine == "pinned")
    return Sweep([("p", CFG, scn)]).run(n_steps=n_steps, dense_rows=rows)[0]


@pytest.mark.parametrize("engine", ["jagged", "segment_sum", "pinned"])
def test_padding_is_inert(engine):
    """Extra PAD flows/hops/links change nothing for the real flows."""
    scn = paper_incast(CFG, roll=0)
    F, H = scn.routes.shape
    L = scn.capacity.shape[0]
    padded = pad_scenario(scn, F + 3, H + 2, L + 5)
    r0 = _run_on(engine, scn, 2000)
    r1 = _run_on(engine, padded, 2000)
    np.testing.assert_array_equal(r0.delivered, r1.delivered[:, :F])
    np.testing.assert_array_equal(r0.inst_thr, r1.inst_thr[:, :F])
    np.testing.assert_array_equal(r0.max_q, r1.max_q)
    # PAD flows do nothing at all
    assert np.all(r1.delivered[:, F:] == 0)
    assert np.all(np.asarray(r1.final.offered)[F:] == 0)


def test_pad_scenario_rejects_shrinking():
    scn = paper_incast(CFG)
    with pytest.raises(ValueError):
        pad_scenario(scn, 1, 1, 1)


# ---------------------------------------------------------------------------
# trace decimation
# ---------------------------------------------------------------------------

def test_trace_every_matches_strided_full_trace():
    scn = paper_incast(CFG, roll=0)
    k = 10
    full = run(scn, CFG, n_steps=2000, trace_every=1)
    dec = run(scn, CFG, n_steps=2000, trace_every=k)
    # cumulative fields: strided samples of the full trace
    np.testing.assert_array_equal(full.delivered[k - 1:: k], dec.delivered)
    np.testing.assert_array_equal(full.rate[k - 1:: k], dec.rate)
    np.testing.assert_array_equal(full.times[k - 1:: k], dec.times)
    # event fields: window sums — totals are exact, not subsampled
    T = full.marked.shape[0]
    np.testing.assert_array_equal(
        full.marked.reshape(T // k, k, -1).sum(1), dec.marked)
    assert full.marked.sum() == dec.marked.sum()
    np.testing.assert_array_equal(
        full.cnp.reshape(T // k, k, -1).sum(1), dec.cnp)
    # gauges: window maxima
    np.testing.assert_array_equal(
        full.max_q.reshape(T // k, k).max(1), dec.max_q)


def test_trace_memory_shrinks():
    """The default 14 ms run's trace footprint drops >= 5x on device."""
    scn = paper_incast(CFG, roll=0)
    full = run(scn, CFG, n_steps=2000, trace_every=1)
    dec = run(scn, CFG, n_steps=2000)          # cfg default trace_every
    bytes_of = lambda r: sum(
        getattr(r, f).nbytes for f in
        ("delivered", "rate", "inst_thr", "max_q", "n_paused", "marked",
         "cnp"))
    assert CFG.sim.trace_every >= 5
    assert bytes_of(full) >= 5 * bytes_of(dec)


def test_n_steps_rounds_up_to_whole_windows():
    scn = paper_incast(CFG, roll=0)
    res = run(scn, CFG, n_steps=995, trace_every=10)
    assert res.delivered.shape[0] == 100       # ceil(995/10) windows
    assert int(res.final.t) == 1000


# ---------------------------------------------------------------------------
# delay line
# ---------------------------------------------------------------------------

def _long_rtt(scn, steps):
    return scn._replace(rtt_steps=np.full_like(scn.rtt_steps, steps))


def test_delay_depth_follows_rtt():
    scn = paper_incast(CFG)
    assert delay_depth(scn) == int(scn.rtt_steps.max()) + 1
    long = _long_rtt(scn, 100)
    assert delay_depth(long) == 101
    st = init_state(long, CFG)
    assert st.trig_buf.shape[0] == 101


def test_legacy_delay_cap_raises_instead_of_wrapping():
    """rtt >= DELAY_SLOTS used to silently alias to rtt % 32."""
    scn = _long_rtt(paper_incast(CFG), 40)
    with pytest.raises(ValueError, match="overflow"):
        make_step_fn(scn, CFG, delay_slots=32)
    with pytest.raises(ValueError, match="overflow"):
        init_state(scn, CFG, delay_slots=32)
    make_step_fn(scn, CFG, delay_slots=64)      # explicit headroom: fine


def test_long_rtt_delays_feedback():
    """A 40-step RTT must react LATER than a 2-step RTT, not (as the
    wrapped legacy path had it) like an 8-step one."""
    cfg = CFG.replace(scheme=CCScheme.DCQCN_REV)
    scn = paper_incast(cfg, roll=0)
    fast = run(scn, cfg, n_steps=2000, trace_every=1)
    slow = run(_long_rtt(scn, 40), cfg, n_steps=2000, trace_every=1)
    f_cut = np.argmax(fast.cnp[:, 0] > 0)       # first CNP arrival
    s_cut = np.argmax(slow.cnp[:, 0] > 0)
    assert f_cut > 0 and s_cut > 0
    assert s_cut >= f_cut + 30                  # ~38 steps more delay


# ---------------------------------------------------------------------------
# ScenarioSpec <-> legacy builders
# ---------------------------------------------------------------------------

def test_legacy_builders_are_spec_wrappers():
    a = paper_incast(CFG, roll=1)
    b = ScenarioSpec.paper_incast(roll=1).build(CFG)
    for fa, fb in zip(a, b):
        if isinstance(fa, np.ndarray):
            np.testing.assert_array_equal(fa, fb)
        else:
            assert fa == fb


def test_spec_is_hashable_plain_data():
    s1 = ScenarioSpec.incast(4)
    s2 = ScenarioSpec.incast(4)
    assert s1 == s2 and hash(s1) == hash(s2)


# ---------------------------------------------------------------------------
# vectorised SimResult metrics (vs reference implementations)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def vol_result():
    cfg = CFG.replace(scheme=CCScheme.DCQCN_REV)
    scn = ScenarioSpec.paper_incast_volume(roll=0).build(cfg)
    return run(scn, cfg, n_steps=6000)


def test_flow_throughput_matches_convolve(vol_result):
    r = vol_result
    w = 100
    k = np.ones(w) / w
    ref = np.stack([np.convolve(r.inst_thr[:, f], k, mode="same")
                    for f in range(r.inst_thr.shape[1])], axis=1)
    np.testing.assert_allclose(r.flow_throughput(w), ref, rtol=1e-6)


def test_completion_times_match_loop_reference(vol_result):
    r = vol_result
    offered = np.asarray(r.final.offered)
    vol = np.asarray(r.scn.volume, dtype=np.float64)
    total = np.where(np.isfinite(vol), vol, offered)
    ref = np.full(total.shape, np.nan)
    for f in range(total.shape[0]):
        if total[f] <= 0:
            continue
        hit = np.nonzero(r.delivered[:, f] >= 0.999 * total[f])[0]
        if hit.size:
            ref[f] = r.times[hit[0]]
    np.testing.assert_allclose(r.completion_times(), ref, equal_nan=True)
