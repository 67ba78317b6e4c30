"""The one jnp fluid step on each of its link-reduction engines: the
jagged layout (``dense_rows=None``), the segment sum (``dense_rows=0``)
and the pinned rectangle (``dense_rows=R``), against the scatter
reference (``reduce="scat"``), through the single-point entry points
(``make_step_fn``, ``simulator.run``) as well as ``Sweep``; and the
generation phase of one step against its formula in NumPy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CCScheme, PAPER_CONFIG, ScenarioSpec, Sweep,
                        dense_reduce_rows, run)
from repro.core.fluid import fluid_step, init_state, make_step_fn, \
    scenario_device, step_params

TRACE_FIELDS = ("delivered", "rate", "inst_thr", "max_q", "n_paused",
                "marked", "cnp", "n_nonmin", "ctrl", "pause_time",
                "vc_stall")


def _assert_trees_equal(a, b, ctx):
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(la) == len(lb)
    for (pa, ga), (pb, gb) in zip(la, lb):
        assert pa == pb
        assert np.array_equal(np.asarray(ga), np.asarray(gb)), \
            (ctx, jax.tree_util.keystr(pa))


def _odd_scn(F):
    """F flows on the paper's CLOS, F off every power of two and block
    size (1, 127, 129, 8193)."""
    pairs = [(i % 16, 16 + (i * 5) % 16) for i in range(F)]
    spec = ScenarioSpec.flows(pairs, t_start=0.0, t_stop=0.5e-3,
                              label=f"odd{F}")
    return spec.build(PAPER_CONFIG)


@pytest.mark.parametrize("F", [1, 127, 129, 8193])
def test_jagged_matches_scat_at_odd_flow_counts(F):
    """The jagged layout at flow counts off every tile boundary: state
    and step trace after a short jitted run equal the scatter
    engine's."""
    scn, cfg = _odd_scn(F), PAPER_CONFIG
    assert dense_reduce_rows(scn) > 0            # the jagged layout runs
    n = 5 if F > 1000 else 20
    finals, traces = [], []
    for kw in (dict(reduce="scat"), dict()):
        step = jax.jit(make_step_fn(scn, cfg, **kw))
        st = init_state(scn, cfg)
        for _ in range(n):
            st, tr = step(st)
        finals.append(st)
        traces.append(tr)
    _assert_trees_equal(finals[0], finals[1], f"F{F}-final")
    _assert_trees_equal(traces[0], traces[1], f"F{F}-trace")


@pytest.mark.parametrize("engine", ["jagged", "segment_sum", "pinned"])
def test_simulator_run_matches_sweep_on_each_engine(engine):
    """``simulator.run``, the single-point entry, equals a ``Sweep``
    launch of the same point on every engine, sample for sample."""
    cfg = PAPER_CONFIG
    scn = ScenarioSpec.paper_incast(
        roll=0, t_start=0.1e-3, t_stop=1.2e-3).build(cfg)
    rows = {"jagged": None, "segment_sum": 0,
            "pinned": dense_reduce_rows(scn)}[engine]
    sweep = Sweep([("p", cfg, scn)])
    static = sweep._prepare(60, 10, dense_rows=rows)[0]
    assert bool(static[5]) == (engine != "segment_sum")
    ra = run(scn, cfg, n_steps=60, trace_every=10)
    rb = sweep.run(n_steps=60, trace_every=10, dense_rows=rows)["p"]
    for f in TRACE_FIELDS:
        assert np.array_equal(np.asarray(getattr(ra, f)),
                              np.asarray(getattr(rb, f))), f
    _assert_trees_equal(ra.final, rb.final, f"sim-vs-sweep-{engine}")


@pytest.mark.parametrize("F", [1, 127, 129, 8193])
def test_generation_phase_matches_numpy(F):
    """One ``fluid_step``'s generation phase (admission into the NIC
    queue, overflow, the notification-window tick) equals its formula
    in NumPy float32, with infinite volumes and buffers among the
    flows.  The sources start at rate 0 and the queues empty, so
    nothing leaves the NIC queue and no notification fires this step."""
    r = np.random.RandomState(F)
    cfg = PAPER_CONFIG.replace(scheme=CCScheme.DCQCN_REV)
    f32 = lambda x: np.asarray(x, np.float32)         # noqa: E731
    gen_rate = f32(r.rand(F) * 12.5e9)
    t_start = f32(r.rand(F) * 2e-3)
    t_stop = f32(np.where(r.rand(F) > 0.5, r.rand(F) * 3e-3, np.inf))
    volume = f32(np.where(r.rand(F) > 0.5, r.rand(F) * 2e7, np.inf))
    nic_buffer = f32(np.where(r.rand(F) > 0.3, r.rand(F) * 2e6, np.inf))
    scn = _odd_scn(F)._replace(gen_rate=gen_rate, t_start=t_start,
                               t_stop=t_stop, volume=volume,
                               nic_buffer=nic_buffer)
    nicq, offered = f32(r.rand(F) * 1e6), f32(r.rand(F) * 1e7)
    dropped, np_tmr = f32(r.rand(F) * 1e5), f32(r.rand(F) * 1e-4)
    t = 1200
    st = init_state(scn, cfg)._replace(
        nicq=jnp.asarray(nicq), offered=jnp.asarray(offered),
        dropped=jnp.asarray(dropped), np_tmr=jnp.asarray(np_tmr),
        rate=jnp.zeros((F,), jnp.float32), t=jnp.asarray(t, jnp.int32))
    new, _ = jax.jit(make_step_fn(scn, cfg))(st)

    dt = np.float32(cfg.sim.dt)
    t_sec = np.float32(t) * dt
    active = (t_sec >= t_start) & (t_sec < t_stop)
    gen = np.where(active, gen_rate, np.float32(0)) * dt
    gen = np.minimum(gen, np.maximum(volume - offered, np.float32(0)))
    q = nicq + gen
    over = np.maximum(q - nic_buffer, np.float32(0))
    want = {"nicq": q - over, "offered": offered + gen - over,
            "dropped": dropped + over, "np_tmr": np_tmr + dt}
    if F > 100:                    # every branch of the formula taken
        assert active.any() and (over > 0).any() and np.isinf(volume).any()
    for name, w in want.items():
        assert np.array_equal(np.asarray(getattr(new, name)), w), (F, name)


@pytest.mark.parametrize("reduce", ["pallas", "dense"])
def test_reduce_takes_two_engines(reduce):
    """``reduce`` is ``"fused"`` or ``"scat"``; anything else is refused,
    through ``make_step_fn`` and by ``fluid_step`` itself."""
    cfg = PAPER_CONFIG
    scn = ScenarioSpec.paper_incast(roll=0).build(cfg)
    with pytest.raises(ValueError, match="reduce"):
        make_step_fn(scn, cfg, reduce=reduce)(init_state(scn, cfg))
    with pytest.raises(ValueError, match="reduce"):
        fluid_step(init_state(scn, cfg), scenario_device(scn),
                   step_params(cfg), dt=float(cfg.sim.dt),
                   n_switches=int(scn.n_switches), reduce=reduce)
