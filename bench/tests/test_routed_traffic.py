"""The multi-path cell's traffic and the 4-chip cell's grid: seeded,
and the same shapes for every seed."""

import numpy as np
import pytest

from bench import harness, traffic
from bench.lookup import module

SEEDS = (1, 2, 3000000017, -5)
drv = module("drivers", "sweep_routed")


@pytest.mark.parametrize("seed", SEEDS)
def test_group_shift_crosses_groups(seed):
    """Every host sends one flow and takes one, each to the next group,
    and each router of the sending and taking group handles as many."""
    cfg = harness.load_config("dfly1056")
    mix = traffic.load("group_shift")
    flows = traffic.scene_flows(mix["scenes"][0], cfg, mix, seed)
    size, groups = 32, 33
    assert len(flows) == 1056
    assert sorted(flows.src) == list(range(1056)) == sorted(flows.dst)
    assert ((flows.src // size + 1) % groups == flows.dst // size).all()
    assert (flows.src // size != flows.dst // size).all()
    assert np.isinf(flows.t_stop).all() and (flows.t_start == 0).all()
    assert (flows.rate == cfg["link"]["line_rate"]).all()


def test_seed_moves_pairs_and_detours():
    cfg = harness.load_config("dfly1056")
    mix = traffic.load("group_shift")
    a, b, c = (traffic.scene_flows(mix["scenes"][0], cfg, mix, s) for s in (7, 7, 8))
    assert np.array_equal(a.dst, b.dst) and not np.array_equal(a.dst, c.dst)
    seeds = {drv.route_seed(s) for s in SEEDS}
    assert len(seeds) == len(SEEDS) and all(0 <= s < 2 ** 31 for s in seeds)


@pytest.mark.parametrize("seed", SEEDS)
def test_routed_points_and_shapes(seed):
    """3 schemes x 3 routing modes, every scenario the same shapes."""
    cfg = harness.load_config("dfly1056")
    mix = traffic.load("group_shift")
    pts = drv.points(mix, cfg, seed)
    assert [p[2] for p in pts] == ["min", "valiant", "ugal"] * 3
    assert len({p[0] for p in pts}) == 9
    _, spec, scn = drv.program_point(cfg, pts[2], seed)
    assert spec.routing == "ugal" and spec.link.n_vcs == 2
    assert (scn.n_paths, scn.vc_mode, scn.route_seed) == (4, "slot", drv.route_seed(seed))


def test_kmin_grid_has_twelve_points():
    cfg = harness.load_config("ft1000")
    mix = traffic.load("a2a_storm_kmin")
    pts = traffic.grid_points(mix, cfg, 3000000017)
    kmax = cfg["dcqcn"]["kmax"]
    assert len(pts) == 12 and len({p[0] for p in pts}) == 12
    kmins = {o["dcqcn.kmin"] for _, _, o, _ in pts}
    assert len(kmins) == 4 and all(kmax / 4 <= k <= kmax for k in kmins)


def test_mesh_warmup_counts_its_compile():
    """A mesh launch caches a jitted callable that compiles at its first
    call: ``compile_s`` reads JAX's compile events of the warm-up there."""
    import jax

    from bench import run as bench_run
    from bench.tests.conftest import DATA
    from repro.core import Sweep
    from repro.dist import sweep_mesh

    cfg = harness.load_config("ft64", DATA)
    mix = dict(traffic.load("a2a_storm_tiny", DATA), n_steps=70, trace_every=10)
    pts = traffic.grid_points(mix, cfg, 5)
    run = bench_run.Run(5, 0.0, False, jax.devices()[:1], harness.Spans(),
                        harness.CompileCounter())
    win = drv.closed_loop(run, mix, lambda: Sweep(
        [(name, harness.cc_spec(cfg, scheme, over), harness.scenario_spec(cfg, flows))
         for name, scheme, over, flows in pts]), dict(mesh=sweep_mesh(1)))
    assert win.ctx["compile_s"] > 0.05
    assert len(win.results) == 1 and win.n_steps == 70
