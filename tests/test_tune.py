"""repro.tune test suite: the soft-model contract and the tuners.

The load-bearing guarantees:

  * **tau = 0 is bitwise hard.**  ``Sweep.run(temperature=0)`` must be
    byte-identical to the default run — the soft relaxations live
    behind ``select(tau, soft, hard)`` with the hard branch verbatim.
  * **tau -> 0 converges.**  On the golden 18-point grid the soft
    model's error against the hard model shrinks monotonically as the
    temperature anneals, hitting exactly zero at tau = 0.
  * **jax.grad is a derivative.**  For every registered objective, the
    gradient through the full dt-scan matches central finite
    differences at random parameter points (direction via cosine
    similarity; the soft model is still piecewise-smooth across
    un-softened transfer plumbing, so FD secants and AD tangents agree
    approximately, not to machine precision).
  * **checkpoint resume is bit-exact.**  A killed-and-resumed tuner
    replays the identical trajectory (``repro.ckpt``; host f64 state,
    per-iteration ``default_rng([seed, it])``).
  * **autotune's verdict is hard.**  The improvement it reports is
    measured on the unsmoothed model via a real ``Sweep`` launch.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp                                    # noqa: E402

from repro.core import (CCScheme, PAPER_CONFIG, ScenarioSpec,  # noqa: E402
                        Sweep)
from repro.core.params import DCQCNParams                  # noqa: E402
from repro.tune import objectives                          # noqa: E402
from repro.tune.optimizers import (BOTuner, ESTuner,       # noqa: E402
                                   Evaluator, GradTuner, ParamBox,
                                   TunableParam, TuneProblem, box_for,
                                   dcqcn_box)
from repro.tune.pareto import autotune, pareto_front       # noqa: E402

# Short-timing incast: flows active 0.1 -> 1.1 ms, so sub-1000-step
# rollouts have real deliveries (the default 1 ms start would make
# every objective degenerate at these horizons).
FAST = dict(t_start=1e-4, t_stop=1.1e-3)
N_STEPS = 900
TRACE_EVERY = 45

DCQCN = PAPER_CONFIG.replace(scheme=CCScheme.DCQCN)


def _small_sweep() -> Sweep:
    return Sweep.grid(
        configs={s.name: PAPER_CONFIG.replace(scheme=s)
                 for s in (CCScheme.DCQCN, CCScheme.DCQCN_REV)},
        scenarios={"in4": ScenarioSpec.incast(4, **FAST)})


def _delivered(res) -> np.ndarray:
    return np.asarray([res[name].final.delivered.sum()
                       for name in sorted(res.summary())])


# ---------------------------------------------------------------------------
# the soft-model contract
# ---------------------------------------------------------------------------


def test_temperature_zero_is_bitwise_hard():
    sweep = _small_sweep()
    hard = sweep.run(n_steps=N_STEPS)
    tau0 = sweep.run(n_steps=N_STEPS, temperature=0.0)
    for name in hard.summary():
        a, b = hard[name], tau0[name]
        assert np.array_equal(np.asarray(a.final.delivered),
                              np.asarray(b.final.delivered)), name
        assert np.array_equal(np.asarray(a.final.rate),
                              np.asarray(b.final.rate)), name
        assert np.array_equal(np.asarray(a.ctrl),
                              np.asarray(b.ctrl)), name


def test_temperature_actually_smooths():
    """tau > 0 must change the dynamics — a soft run that equals the
    hard one means the temperature never reached the gates."""
    sweep = _small_sweep()
    hard = _delivered(sweep.run(n_steps=N_STEPS))
    soft = _delivered(sweep.run(n_steps=N_STEPS, temperature=0.3))
    assert not np.allclose(hard, soft, rtol=1e-6)


def test_annealing_converges_on_golden_grid():
    """The golden 18-point grid (3 schemes x 2 fabrics x 3 routings):
    soft-vs-hard delivered-bytes error decreases as tau anneals and is
    exactly zero at tau = 0."""
    from test_golden import _grid
    sweep = _grid()
    ref = _delivered(sweep.run(n_steps=300))
    errs = {}
    for tau in (0.5, 0.2, 0.08, 0.0):
        d = _delivered(sweep.run(n_steps=300, temperature=tau))
        errs[tau] = float(np.mean(np.abs(d - ref) / (np.abs(ref) + 1.0)))
    assert errs[0.0] == 0.0
    assert errs[0.08] < errs[0.5]
    # weak per-stage monotonicity (10% slack for non-uniform sites)
    assert errs[0.2] <= errs[0.5] * 1.10 + 1e-12
    assert errs[0.08] <= errs[0.2] * 1.10 + 1e-12


@pytest.mark.parametrize("engine", ["segment_sum", "pinned", "scat"])
def test_soft_dynamics_agree_on_every_engine(engine):
    """The soft gates ride every link-reduction engine alike: a
    ``temperature > 0`` sweep on the segment sum, the pinned rectangle
    or the scatter engine is bitwise the default jagged layout's."""
    from repro.core import dense_reduce_rows
    sweep = _small_sweep()
    kw = {"segment_sum": dict(dense_rows=0),
          "pinned": dict(dense_rows=max(dense_reduce_rows(p.scenario)
                                        for p in sweep.points)),
          "scat": dict(reduce="scat")}[engine]
    a = sweep.run(n_steps=64, temperature=0.1)
    b = sweep.run(n_steps=64, temperature=0.1, **kw)
    for x, y in zip(jax.tree.leaves((a.traces, a.final)),
                    jax.tree.leaves((b.traces, b.final))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# gradients vs finite differences
# ---------------------------------------------------------------------------


def _soft_values(ev: Evaluator, thetas: np.ndarray,
                 tau: float) -> np.ndarray:
    """[B] soft objective values in ONE vmapped launch (FD probe)."""
    from repro.core.fluid import fluid_step
    from repro.core.simulator import decimating_scan

    def loss(theta):
        par = ev.box.apply(ev.par0, theta)
        par = par._replace(temperature=jnp.asarray(tau, jnp.float32))
        step = lambda s: fluid_step(s, ev.sd, par, dt=ev.dt,
                                    n_switches=ev.n_sw,
                                    reduce="fused", dense_blocks=())
        final, tr = decimating_scan(step, ev.st0, ev.n_samples, ev.k,
                                    ev.dt)
        return ev.obj_fn(final, tr, ev.ctx)

    return np.asarray(jax.jit(jax.vmap(loss))(
        jnp.asarray(thetas, jnp.float32)), np.float64)


@pytest.mark.parametrize("objective", sorted(objectives.OBJECTIVES))
def test_grad_matches_central_fd(objective):
    """AD through the dt-scan vs central differences at 5 random
    thetas.  Gates are directional (cosine) plus a loose magnitude
    band, applied only where BOTH estimators see a real gradient: the
    un-softened transfer plumbing keeps the model piecewise-smooth, so
    at near-flat points FD measures kink secants (O(1e-3)) while AD
    correctly reports ~0 — those points are gated on AD flatness
    instead."""
    tau, h, n_points = 0.25, 0.05, 5
    ev = Evaluator(TuneProblem(
        DCQCN, ScenarioSpec.incast(4), objective=objective,
        n_steps=1500, trace_every=50))
    d = ev.box.d
    rng = np.random.default_rng(7)
    pts = rng.standard_normal((n_points, d))

    # one vmapped launch for every (point, coordinate, +/-) probe
    probes = np.stack([p + s * h * np.eye(d)[i]
                       for p in pts for i in range(d) for s in (+1, -1)])
    vals = _soft_values(ev, probes, tau).reshape(n_points, d, 2)
    fd = (vals[:, :, 0] - vals[:, :, 1]) / (2 * h)

    cosines, flat_ad = [], []
    for p, f in zip(pts, fd):
        _, g = ev.value_and_grad(p, tau)
        assert np.all(np.isfinite(g)), (objective, p, g)
        ng, nf = np.linalg.norm(g), np.linalg.norm(f)
        if min(ng, nf) < 1e-3:
            flat_ad.append(ng)            # kink-noise regime for FD
            continue
        cosines.append(float(np.dot(g, f) / (ng * nf)))
        assert 0.05 < ng / nf < 20.0, (objective, p, ng, nf)
    if cosines:
        assert np.mean(cosines) > 0.85, (objective, cosines)
        assert min(cosines) > 0.6, (objective, cosines)
    else:
        # genuinely flat objective at every probe: AD must agree
        assert max(flat_ad) < 1e-2, (objective, flat_ad)


# ---------------------------------------------------------------------------
# DCQCNParams construction-time validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bad", [
    dict(kmin=20e3, kmax=10e3),
    dict(pmax=0.0), dict(pmax=1.5), dict(pmax=-0.1),
    dict(g=0.0), dict(g=1.5),
    dict(rai=-1.0), dict(rhai=-1.0), dict(timer_T=-1e-6),
    dict(byte_counter_B=-1.0), dict(min_rate=-1.0), dict(cnp_window=-1.0),
    dict(rate_decrease_factor=-0.1), dict(rate_decrease_factor=1.5),
])
def test_dcqcn_params_rejects(bad):
    with pytest.raises(ValueError):
        DCQCNParams(**bad)


def test_dcqcn_params_accepts_edges():
    DCQCNParams(pmax=1.0, g=1.0, rate_decrease_factor=0.0)
    DCQCNParams(kmin=10e3, kmax=10e3)          # step marking


# ---------------------------------------------------------------------------
# ParamBox
# ---------------------------------------------------------------------------


def test_param_box_encode_roundtrip():
    box = dcqcn_box()
    spec = DCQCN.to_spec()
    theta = box.encode(spec)
    vals = box.values(theta, xp=np)
    want = {"V": spec.dcqcn.kmin, "rdf": spec.dcqcn.rate_decrease_factor,
            "g": spec.dcqcn.g, "rai": spec.dcqcn.rai}
    for name, v in zip(box.names, vals):
        np.testing.assert_allclose(v, want[name], rtol=1e-4)


def test_param_box_host_and_trace_values_agree():
    box = dcqcn_box()
    theta = np.asarray([0.7, -1.2, 0.3, 2.0])
    np.testing.assert_allclose(
        box.values(theta.astype(np.float32), xp=np),
        np.asarray(box.values(jnp.asarray(theta, jnp.float32))),
        rtol=1e-6)


def test_param_box_to_spec_multi_path_validation():
    """Regression: the V knob writes (kmin, kmax) together.  Writing
    them one at a time used to trip the kmin <= kmax validator on the
    transient state whenever V moved past the old kmax."""
    box = dcqcn_box()
    spec = DCQCN.to_spec()
    for t in (+6.0, -6.0):                 # push V to both box edges
        theta = box.encode(spec)
        theta[list(box.names).index("V")] = t
        out = box.to_spec(spec, theta)
        assert out.dcqcn.kmin == out.dcqcn.kmax
    hi = box.to_spec(spec, np.full(box.d, 6.0))
    assert hi.dcqcn.kmin > spec.dcqcn.kmax


def test_param_box_consistency_check_fires():
    """A knob whose spec path and StepParams leaf disagree must raise,
    not silently tune a different constant than it reports."""
    box = ParamBox((TunableParam(
        "wrong", ("react.rp_g",), ("dcqcn.rai",), 1e6, 2e8, log=True),))
    with pytest.raises(AssertionError, match="box inconsistency"):
        box.to_spec(DCQCN.to_spec(), np.zeros(1))


def test_box_for_dispatch():
    assert box_for(DCQCN).names == dcqcn_box().names
    assert "thresh" in box_for(PAPER_CONFIG).names
    swift = PAPER_CONFIG.to_spec().replace(reaction="swift")
    with pytest.raises(ValueError, match="no default ParamBox"):
        box_for(swift)


# ---------------------------------------------------------------------------
# checkpointed tuner loops (bit-exact resume)
# ---------------------------------------------------------------------------


def _tiny_problem(objective="default"):
    return TuneProblem(DCQCN, ScenarioSpec.incast(3, **FAST),
                       objective=objective, n_steps=N_STEPS,
                       trace_every=TRACE_EVERY)


def test_grad_tuner_resume_bit_exact(tmp_path):
    ev = Evaluator(_tiny_problem())
    full = GradTuner(iters=4, lr=0.2, temperature=0.3).run(ev, seed=0)
    d = str(tmp_path / "grad")
    GradTuner(iters=2, lr=0.2, temperature=0.3).run(
        ev, seed=0, ckpt_dir=d, ckpt_every=2)
    resumed = GradTuner(iters=4, lr=0.2, temperature=0.3).run(
        ev, seed=0, ckpt_dir=d)
    assert np.array_equal(full.theta, resumed.theta)
    assert np.array_equal(full.value, resumed.value)


def test_es_tuner_resume_bit_exact(tmp_path):
    ev = Evaluator(_tiny_problem())
    tuner = dict(iters=3, pop=4, sigma=0.3, lr=0.4)
    full = ESTuner(**tuner).run(ev, seed=1)
    d = str(tmp_path / "es")
    ESTuner(**dict(tuner, iters=2)).run(ev, seed=1, ckpt_dir=d,
                                        ckpt_every=2)
    resumed = ESTuner(**tuner).run(ev, seed=1, ckpt_dir=d)
    assert np.array_equal(full.theta, resumed.theta)
    assert np.array_equal(full.value, resumed.value)


def test_bo_tuner_smoke():
    ev = Evaluator(_tiny_problem())
    trace = BOTuner(iters=2, init=3, q=1, cand=32).run(ev, seed=0)
    assert trace.theta.shape[1] == ev.box.d
    assert len(trace.value) >= 5                  # 3 init + 2 x >=1
    assert np.all(np.isfinite(trace.value))
    assert trace.best.shape == (ev.box.d,)


# ---------------------------------------------------------------------------
# objectives + metrics plumbing
# ---------------------------------------------------------------------------


def test_resolve_objective_forms():
    fn, sig = objectives.resolve("goodput")
    assert sig == "name:goodput"
    _, sig = objectives.resolve({"goodput": 1, "jain": 0.5})
    assert sig.startswith("weighted:")
    _, sig = objectives.resolve("default")
    assert sig.startswith("weighted:")
    with pytest.raises(KeyError):
        objectives.resolve("nope")
    with pytest.raises(KeyError):
        objectives.weighted({"nope": 1.0})


def test_summary_carries_tuner_metrics():
    res = _small_sweep().run(n_steps=N_STEPS)
    for name, row in res.summary().items():
        assert 0.0 <= row["jain_index"] <= 1.0, name
        assert row["p99_slowdown"] >= 1.0, name
        assert np.isfinite(row["ctrl_per_mb"]), name
        assert row["ctrl_per_mb"] >= 0.0, name


def test_hard_objective_consistent_with_soft_at_tau0():
    """The device (soft-path) objective at tau = 0 and the host
    hard_objective score the SAME rollout: they must agree closely
    (both are f32 pipelines, not bit-identical reductions)."""
    ev = Evaluator(_tiny_problem())
    theta = ev.box.encode(ev.spec)
    v_soft, _ = ev.value_and_grad(theta, 0.0)
    v_hard = float(ev.hard_values(theta[None])[0])
    np.testing.assert_allclose(v_soft, v_hard, rtol=2e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# pareto + autotune
# ---------------------------------------------------------------------------


def test_pareto_front_basic():
    vals = np.asarray([[1.0, 1.0], [2.0, 0.5], [0.5, 2.0],
                       [0.9, 0.9], [2.0, 0.5]])
    keep = pareto_front(vals)
    assert 3 not in keep                       # dominated by [1, 1]
    assert {0, 1, 2} <= set(keep.tolist())
    assert 4 in keep                           # duplicates both survive
    # mixed senses: column 1 is a cost
    keep = pareto_front(np.asarray([[1.0, 5.0], [1.0, 2.0]]),
                        senses=[1, -1])
    assert keep.tolist() == [1]
    with pytest.raises(ValueError):
        pareto_front(np.zeros(3))


def test_autotune_improves_dcqcn_incast():
    """The PR's acceptance check: GradTuner on the CLOS incast finds
    DCQCN constants whose HARD-model objective strictly beats the
    paper defaults (verdict from an unsmoothed Sweep launch)."""
    res = autotune(DCQCN, ScenarioSpec.incast(8), method="grad",
                   n_steps=3000, trace_every=50, iters=12, lr=0.25,
                   temperature=0.2, seed=0)
    assert res.improved, (res.baseline_value, res.best_value)
    assert res.best_value > res.baseline_value
    assert res.best_metrics["goodput"] > res.baseline_metrics["goodput"]
    assert set(res.best_params) == set(dcqcn_box().names)
    # the winner must be a valid, constructible config
    assert res.best_cfg.dcqcn.kmin == res.best_cfg.dcqcn.kmax
    rec = res.to_record()
    assert rec["improved"] and rec["best_value"] == res.best_value
    import json
    json.dumps(rec)                            # JSON-serialisable


def test_autotune_es_smoke():
    res = autotune(DCQCN, ScenarioSpec.incast(3, **FAST), method="es",
                   n_steps=N_STEPS, trace_every=TRACE_EVERY,
                   iters=2, pop=4, seed=0, max_candidates=4)
    assert res.method == "es"
    assert res.best_value >= res.baseline_value   # argmax includes base
    assert len(res.candidate_values) == len(res.candidates)


def test_autotune_unknown_method():
    with pytest.raises(KeyError, match="unknown method"):
        autotune(DCQCN, ScenarioSpec.incast(3), method="nope")
