"""Composable CC-stage API: registry mechanics, construction-time
validation, the three new stage variants (slope / fncc / swift), and
the acceptance property — a mixed stage matrix riding ONE jit."""

import dataclasses

import numpy as np
import pytest

from repro.core import (CCScheme, CCSpec, DCQCNParams, LinkParams,
                        PAPER_CONFIG, ScenarioSpec, SimParams, Sweep, cc,
                        dense_reduce_rows, run)

SCENE = ScenarioSpec.paper_incast(roll=0, t_start=0.1e-3, t_stop=1.2e-3)


# ---------------------------------------------------------------------------
# construction-time validation
# ---------------------------------------------------------------------------

def test_pfc_thresholds_validated():
    with pytest.raises(ValueError, match="XOFF.*XON|pfc_xoff"):
        LinkParams(pfc_xoff_frac=0.4, pfc_xon_frac=0.5)
    with pytest.raises(ValueError, match="pfc_xoff"):
        LinkParams(pfc_xoff_frac=0.5, pfc_xon_frac=0.5)
    LinkParams(pfc_xoff_frac=0.51, pfc_xon_frac=0.5)     # ok


def test_marking_ramp_validated():
    with pytest.raises(ValueError, match="kmin.*kmax"):
        DCQCNParams(kmin=16 * 1024.0, kmax=15 * 1024.0)
    DCQCNParams(kmin=15 * 1024.0, kmax=15 * 1024.0)      # step: ok


def test_unknown_stage_names_raise():
    with pytest.raises(ValueError, match="unknown marking stage"):
        CCSpec(marking="nope")
    with pytest.raises(ValueError, match="unknown notification stage"):
        CCSpec(notification="nope")
    with pytest.raises(ValueError, match="unknown reaction stage"):
        CCSpec(reaction="nope")
    with pytest.raises(ValueError, match="unknown routing"):
        CCSpec(routing="nope")


def test_adaptive_routing_needs_multipath_scenario():
    """routing != 'min' on a single-path scenario must raise instead of
    silently degenerating to minimal routing — in run() AND in Sweep."""
    cfg = PAPER_CONFIG.replace(routing="ugal")
    scn = SCENE.build(cfg)                     # n_paths = 1
    with pytest.raises(ValueError, match="multi-path"):
        run(scn, cfg, n_steps=10)
    with pytest.raises(ValueError, match="multi-path"):
        Sweep([("p", cfg, scn)])
    # multi-path scenario: fine
    multi = ScenarioSpec.permutation(
        8, seed=0, n_paths=4, t_stop=0.3e-3).build(cfg)
    Sweep([("p", cfg, multi)])


# ---------------------------------------------------------------------------
# registry mechanics
# ---------------------------------------------------------------------------

def test_builtin_codes_are_frozen():
    assert [s.name for s in cc.MARKING.stages()] == ["cp", "ecp", "slope"]
    assert [s.name for s in cc.NOTIFICATION.stages()] == \
        ["np", "enp", "fncc"]
    assert [s.name for s in cc.REACTION.stages()] == \
        ["pfc", "rp", "erp", "swift"]
    assert cc.MARKING.code("cp") == 0 and cc.REACTION.code("swift") == 3


def test_register_rejects_duplicates_and_param_conflicts():
    reg = cc.StageRegistry("test")
    reg.register("a", step=lambda p, c, s: ((), {}),
                 params={"shared": lambda spec: 1.0})
    with pytest.raises(ValueError, match="already registered"):
        reg.register("a", step=lambda p, c, s: ((), {}))
    reg.register("b", step=lambda p, c, s: ((), {}),
                 params={"shared": lambda spec: 2.0})
    with pytest.raises(ValueError, match="conflicting"):
        reg.device_params(PAPER_CONFIG.to_spec())


def test_registered_state_rides_fluid_state():
    """Every stage's init_state contributes to FluidState.cc with [F]
    leaves, for every config (the pytree must be sweep-stable)."""
    from repro.core.fluid import init_state
    scn = SCENE.build(PAPER_CONFIG)
    st = init_state(scn, PAPER_CONFIG)
    assert set(st.cc) == {"slope_acc", "swift_cool"}
    for v in st.cc.values():
        assert v.shape == (scn.routes.shape[0],)


# ---------------------------------------------------------------------------
# slope marking (kmin < kmax ramp, pmax)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def slope_vs_cp():
    ramp = DCQCNParams(kmin=15 * 1024.0, kmax=90 * 1024.0, pmax=0.3)
    base = CCSpec(notification="enp", reaction="erp", dcqcn=ramp)
    res = Sweep.grid(
        configs={"cp": base.replace(marking="cp"),
                 "slope": base.replace(marking="slope")},
        scenarios={"hol": SCENE}).run(n_steps=2500)
    return res


def test_slope_marks_probabilistically(slope_vs_cp):
    """With a real kmin<kmax ramp and pmax<1, slope marking thins the
    mark stream relative to step marking at the same kmin — but still
    marks (the loop stays closed) and still controls the queue."""
    cp, slope = slope_vs_cp["cp/hol"], slope_vs_cp["slope/hol"]
    m_cp, m_slope = int(cp.marked.sum()), int(slope.marked.sum())
    assert 0 < m_slope < 0.8 * m_cp, (m_slope, m_cp)
    # queue stays bounded well below the PFC pause point
    assert float(slope.max_q.max()) < 0.9 * 512 * 1024


def test_slope_with_step_params_degenerates_to_cp():
    """kmin == kmax (the paper's V) makes the ramp a step of p=1 — the
    error-diffusion accumulator fires every step, so slope == cp
    bit-exactly (the shim's safety net for default params)."""
    base = CCSpec(notification="enp", reaction="erp")
    res = Sweep.grid(
        configs={"cp": base.replace(marking="cp"),
                 "slope": base.replace(marking="slope")},
        scenarios={"hol": SCENE}).run(n_steps=1200)
    a, b = res["cp/hol"], res["slope/hol"]
    for f in ("delivered", "rate", "marked", "cnp", "max_q"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), f)


# ---------------------------------------------------------------------------
# FNCC in-path notification
# ---------------------------------------------------------------------------

def _first_event_ms(res, field) -> float:
    ev = np.asarray(getattr(res, field)).sum(axis=1) > 0
    return float(res.times[np.argmax(ev)]) if ev.any() else np.inf


@pytest.fixture(scope="module")
def fncc_vs_enp():
    # 0.1 us integrator: the CNP feedback delay spans ~23 steps, so the
    # in-path shortcut is resolvable (at dt = 1 us the whole RTT rounds
    # down to the 2-step floor and fncc == enp by construction)
    sim = SimParams(dt=1e-7, trace_every=1)
    base = CCSpec(marking="ecp", reaction="erp", sim=sim)
    scene = ScenarioSpec.paper_incast(roll=0, t_start=0.02e-3,
                                      t_stop=0.5e-3)
    return Sweep.grid(
        configs={"enp": base.replace(notification="enp"),
                 "fncc": base.replace(notification="fncc")},
        scenarios={"hol": scene}).run(n_steps=2500)


def test_fncc_feedback_arrives_earlier(fncc_vs_enp):
    """Same marking stream, but the first CNP lands strictly earlier
    through the in-path return than through the end-to-end echo."""
    enp, fncc = fncc_vs_enp["enp/hol"], fncc_vs_enp["fncc/hol"]
    t_mark_enp = _first_event_ms(enp, "marked")
    t_mark_fncc = _first_event_ms(fncc, "marked")
    assert t_mark_enp == t_mark_fncc          # detection unchanged
    t_enp, t_fncc = _first_event_ms(enp, "cnp"), \
        _first_event_ms(fncc, "cnp")
    assert np.isfinite(t_enp) and np.isfinite(t_fncc)
    assert t_fncc < t_enp, (t_fncc, t_enp)


def test_fncc_never_slower_than_rtt(fncc_vs_enp):
    """The shortened delay is clipped to [2 steps, rtt] — peak queue
    under faster feedback must not blow past the end-to-end variant's
    by more than noise (the loop is strictly tighter)."""
    enp, fncc = fncc_vs_enp["enp/hol"], fncc_vs_enp["fncc/hol"]
    assert float(fncc.max_q.max()) <= 1.1 * float(enp.max_q.max())


# ---------------------------------------------------------------------------
# swift delay-target reaction
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def swift_res():
    base = CCSpec(marking="ecp", notification="enp")
    return Sweep.grid(
        configs={"swift": base.replace(reaction="swift"),
                 "pfc": base.replace(reaction="pfc"),
                 "swift_np": base.replace(reaction="swift",
                                          notification="np")},
        scenarios={"hol": SCENE}).run(n_steps=2500)


def test_swift_throttles_on_delay_not_marks(swift_res):
    """The delay-target reaction must actually throttle (rates fall
    below line) and keep queues far below the uncontrolled PFC-only
    run — despite never consuming a CNP."""
    swift, pfc = swift_res["swift/hol"], swift_res["pfc/hol"]
    line = PAPER_CONFIG.link.line_rate
    assert float(np.asarray(swift.final.rate)[:4].max()) < 0.6 * line
    assert float(np.asarray(pfc.final.rate).min()) >= line * 0.99
    assert float(swift.max_q.max()) < 0.75 * float(pfc.max_q.max())
    assert float(np.asarray(swift.final.delivered).sum()) > 0


def test_swift_is_notification_independent(swift_res):
    """Swapping the notification stage under swift changes which CNPs
    fly, but not a single delivered byte or rate sample — reaction
    composability is real, not nominal."""
    a, b = swift_res["swift/hol"], swift_res["swift_np/hol"]
    for f in ("delivered", "rate", "inst_thr", "max_q"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), f)


# ---------------------------------------------------------------------------
# acceptance: a mixed stage matrix rides ONE jit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["jagged", "segment_sum", "pinned"])
def test_mixed_stage_matrix_one_jit_no_recompile(engine):
    """>= 3 marking x 2 notification x 3 reaction variants — including
    slope, fncc and swift — in a single Sweep launch with exactly one
    executable build on each link-reduction engine (the jagged layout,
    the segment sum, the pinned rectangle), and the stage axes must be
    live (outputs differ across combinations)."""
    from repro.core.experiments import SWEEP_EXEC_CACHE
    ramp = DCQCNParams(kmin=15 * 1024.0, kmax=90 * 1024.0, pmax=0.3)
    combos = [(m, n, r)
              for m in ("cp", "ecp", "slope")
              for n in ("enp", "fncc")
              for r in ("rp", "erp", "swift")]
    configs = {f"{m}+{n}+{r}": CCSpec(marking=m, notification=n,
                                      reaction=r, dcqcn=ramp)
               for m, n, r in combos}
    sweep = Sweep.grid(configs=configs, scenarios={"hol": SCENE})
    rows = {"jagged": None, "segment_sum": 0,
            "pinned": max(dense_reduce_rows(p.scenario)
                          for p in sweep.points)}[engine]
    assert rows != 0 or engine == "segment_sum"
    before = SWEEP_EXEC_CACHE.stats()
    res = sweep.run(n_steps=1200, dense_rows=rows)
    assert (SWEEP_EXEC_CACHE.stats() - before).misses <= 1, \
        "mixed stage matrix must share one compiled executable"
    assert len(res) == 18
    delivered = {name: round(float(np.asarray(r.final.delivered).sum()))
                 for name, r in res.items()}
    # marking axis live (under erp), notification axis live via mark
    # counts, reaction axis live
    assert delivered["cp+enp+erp/hol"] != delivered["ecp+enp+erp/hol"]
    assert delivered["ecp+enp+erp/hol"] != delivered["ecp+enp+swift/hol"]
    marks = {name: int(r.marked.sum()) for name, r in res.items()}
    assert marks["slope+enp+erp/hol"] != marks["cp+enp+erp/hol"]


def test_shim_and_spec_share_the_one_jit():
    """Legacy CCConfig points and CCSpec points can ride the same
    launch — the shim is a mapping, not a second code path."""
    cfg = PAPER_CONFIG.replace(scheme=CCScheme.DCQCN)
    spec = CCSpec(marking="cp", notification="np", reaction="rp")
    res = Sweep([("legacy", cfg, SCENE), ("spec", spec, SCENE)]).run(
        n_steps=1200)
    np.testing.assert_array_equal(res["legacy"].delivered,
                                  res["spec"].delivered)


def test_config_grid_sweeps_stage_params():
    """Dotted-path grids reach the new stage param groups too."""
    from repro.core import config_grid
    grid = config_grid(CCSpec(reaction="swift"),
                       **{"swift.target_delay": [2e-6, 8e-6]})
    res = Sweep.grid(configs=grid, scenarios={"hol": SCENE}).run(
        n_steps=1500)
    qs = [float(r.max_q.max()) for _, r in res.items()]
    assert qs[0] < qs[1]        # tighter delay target -> smaller queues


def test_ccspec_is_frozen_and_replaceable():
    s = CCSpec()
    assert s.name == "ecp+enp+erp"
    s2 = s.replace(marking="slope",
                   dcqcn=DCQCNParams(kmax=60 * 1024.0))
    assert s2.marking == "slope" and s.marking == "ecp"
    with pytest.raises(dataclasses.FrozenInstanceError):
        s.marking = "cp"


# ---------------------------------------------------------------------------
# the reaction stages against their oracles (repro.kernels.ref)
# ---------------------------------------------------------------------------

def _react_ctx(F, seed, cnp_p=0.5):
    """Random reaction-point state and feedback for F flows, the hard
    model's (temperature 0)."""
    import jax.numpy as jnp
    r = np.random.RandomState(seed)
    f32 = lambda x: jnp.asarray(x, jnp.float32)       # noqa: E731
    return cc.ReactCtx(
        rate=f32(r.rand(F) * 12.5e9), rp_target=f32(r.rand(F) * 12.5e9),
        alpha=f32(r.rand(F)), byte_cnt=f32(r.rand(F) * 10e6),
        tmr=f32(r.rand(F) * 55e-6), alpha_tmr=f32(r.rand(F) * 55e-6),
        bc_stage=jnp.asarray(r.randint(0, 8, F), jnp.int32),
        t_stage=jnp.asarray(r.randint(0, 8, F), jnp.int32),
        hold=f32(np.where(r.rand(F) > 0.5, r.rand(F) * 1e-4, 0.0)),
        cnp=f32(r.rand(F) < cnp_p), tgt_rx=f32(r.rand(F) * 12.5e9),
        qdelay=f32(np.where(r.rand(F) > 0.3, r.rand(F) * 2e-5, 0.0)),
        jitter=f32(r.rand(F) * 2 - 1), gen_rate=f32(r.rand(F) * 12.5e9),
        line_rate=f32(PAPER_CONFIG.link.line_rate),
        dt=f32(PAPER_CONFIG.sim.dt), tau=f32(0.0))


def _dispatch_reaction(name, ctx, state):
    """The reaction family dispatched as the step dispatches it: every
    stage evaluated, one selected by its traced code."""
    import jax
    import jax.numpy as jnp
    from repro.core.fluid import step_params
    react = step_params(CCSpec(reaction=name)).react
    fn = jax.jit(lambda code, p, c, s: cc.dispatch(cc.REACTION, code, p,
                                                   c, s))
    out, upd = fn(jnp.asarray(cc.REACTION.code(name), jnp.int32), react,
                  ctx, state)
    return out, upd, {k: float(v) for k, v in react.items()}


def _same(a, b, what):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), what)


@pytest.mark.parametrize("F", [1, 5, 127, 129, 130, 8192, 8193, 100_001])
def test_rp_stage_matches_oracle(F):
    """The ``rp`` stage (DCQCN's alpha EWMA and staged recovery) equals
    ``rp_update_ref`` field for field."""
    from repro.kernels import ref
    ctx = _react_ctx(F, F, cnp_p=0.4)
    out, _, p = _dispatch_reaction("rp", ctx, {"swift_cool": ctx.hold})
    import jax
    # the oracle compiled as the stage is: each side's multiply-adds
    # then round alike on a CPU's vector units
    want = jax.jit(ref.rp_update_ref, static_argnums=2)(
        ref.RPState(ctx.rate, ctx.rp_target, ctx.alpha, ctx.byte_cnt,
                    ctx.tmr, ctx.alpha_tmr,
                    ctx.bc_stage.astype(np.float32),
                    ctx.t_stage.astype(np.float32)),
        ctx.cnp > 0,
        ref.RPParams(g=p["rp_g"], rate_decrease=p["rp_rdf"],
                     timer_T=p["rp_timer"], byte_B=p["rp_byte"],
                     rai=p["rp_rai"], rhai=p["rp_rhai"],
                     fr_stages=p["rp_fr_stages"], min_rate=p["rp_min_rate"],
                     line_rate=float(ctx.line_rate), dt=float(ctx.dt)))
    for got, w, name in zip(
            (out.rate, out.rp_target, out.alpha, out.byte_cnt, out.tmr,
             out.alpha_tmr, out.bc_stage.astype(np.float32),
             out.t_stage.astype(np.float32)),
            want, ref.RPState._fields):
        _same(got, w, f"F={F} {name}")
    assert np.asarray(ctx.cnp).any() or F < 5


@pytest.mark.parametrize("F", [1, 127, 129, 8193, 50_000])
def test_erp_stage_matches_oracle(F):
    """The ``erp`` stage (settle to the signalled share, hold, jittered
    additive recovery) equals ``erp_update_ref``."""
    import jax.numpy as jnp
    from repro.kernels import ref
    ctx = _react_ctx(F, 7)
    out, _, p = _dispatch_reaction("erp", ctx, {"swift_cool": ctx.hold})
    slope = p["erp_rai"] * (1.0 + p["erp_jitter"] * ctx.jitter)
    rate, hold = ref.erp_update_ref(
        ctx.rate, ctx.hold, ctx.cnp > 0, ctx.tgt_rx, jnp.asarray(slope),
        ref.ERPParams(settle=p["erp_settle"], hold=p["erp_hold"],
                      min_rate=p["erp_min_rate"],
                      line_rate=float(ctx.line_rate), dt=float(ctx.dt)))
    _same(out.rate, rate, f"F={F} rate")
    _same(out.hold, hold, f"F={F} hold")


@pytest.mark.parametrize("F", [1, 127, 129, 8193, 50_000])
def test_swift_stage_matches_oracle(F):
    """The ``swift`` stage (delay-target decrease paced by its guard,
    additive recovery) equals ``swift_update_ref``, its cool-down
    timer included."""
    from repro.kernels import ref
    ctx = _react_ctx(F, 11)
    cool = ctx.hold                     # zeros and positive timers mixed
    out, upd, p = _dispatch_reaction("swift", ctx, {"swift_cool": cool})
    rate, cool_w = ref.swift_update_ref(
        ctx.rate, cool, ctx.qdelay, target=p["swift_target"],
        beta=p["swift_beta"], ai=p["swift_ai"], guard=p["swift_guard"],
        min_rate=p["swift_min_rate"], line_rate=float(ctx.line_rate),
        dt=float(ctx.dt))
    _same(out.rate, rate, f"F={F} rate")
    _same(upd["swift_cool"], cool_w, f"F={F} swift_cool")


def test_reaction_params_ride_a_traced_grid():
    """The stage constants are traced data: a grid of ERP parameters
    vmapped through one jitted dispatch traces once, and each row equals
    the oracle under that row's constants."""
    import jax
    import jax.numpy as jnp
    from repro.core import RevParams
    from repro.core.fluid import step_params
    from repro.kernels import ref
    F = 300
    ctx = _react_ctx(F, 3)
    grid = [CCSpec(reaction="erp", rev=RevParams(erp_settle=s, erp_rai=a))
            for s, a in ((0.98, 5e12), (0.5, 5e12), (0.75, 1e12))]
    react = jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[step_params(g).react for g in grid])
    traces = []

    @jax.jit
    def f(code, p):
        traces.append(None)            # traces once per shape, not per value
        return jax.vmap(lambda q: cc.dispatch(
            cc.REACTION, code, q, ctx, {"swift_cool": ctx.hold})[0])(p)

    code = jnp.asarray(cc.REACTION.code("erp"), jnp.int32)
    out = f(code, react)
    f(code, jax.tree.map(lambda x: x[::-1], react))
    assert len(traces) == 1
    rates = np.asarray(out.rate)
    assert not np.array_equal(rates[0], rates[1])
    for i in range(len(grid)):
        p = {k: float(v[i]) for k, v in react.items()}
        slope = p["erp_rai"] * (1.0 + p["erp_jitter"] * ctx.jitter)
        want, _ = ref.erp_update_ref(
            ctx.rate, ctx.hold, ctx.cnp > 0, ctx.tgt_rx, slope,
            ref.ERPParams(settle=p["erp_settle"], hold=p["erp_hold"],
                          min_rate=p["erp_min_rate"],
                          line_rate=float(ctx.line_rate),
                          dt=float(ctx.dt)))
        _same(rates[i], want, f"grid row {i}")
