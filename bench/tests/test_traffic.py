"""The traffic generator: seeded, and the same shapes for every seed."""

import numpy as np

from bench import harness, traffic
from conftest import DATA


def test_relabel_keeps_link_loads():
    """A seed relabels hosts by a fat-tree automorphism: every seed puts
    the same multiset of flow counts on the links, so the program's
    dense-reduction row count (its static shape) never changes."""
    from repro.core import dense_reduce_rows
    cfg = harness.load_config("ft64", DATA)
    mix = traffic.load("a2a_storm_tiny", DATA)
    rows, loads = set(), set()
    for seed in (1, 2, 3000000017, -5):
        (_, scheme, over, flows), *_ = traffic.grid_points(mix, cfg, seed)
        scn = harness.scenario_spec(cfg, flows).build(harness.cc_spec(cfg, scheme, over))
        rows.add(dense_reduce_rows(scn))
        counts = np.bincount(scn.routes[scn.routes >= 0])
        loads.add(tuple(sorted(counts[counts > 0])))
    assert len(rows) == 1 and len(loads) == 1


def test_seed_determinism_and_change():
    cfg = harness.load_config("ft1000")
    mix = traffic.load("a2a_storm")
    a = traffic.scene_flows(mix["scenes"][0], cfg, mix, 7)
    b = traffic.scene_flows(mix["scenes"][0], cfg, mix, 7)
    c = traffic.scene_flows(mix["scenes"][0], cfg, mix, 8)
    assert len(a) == 4288 and np.array_equal(a.src, b.src)
    assert not np.array_equal(a.src, c.src)
    assert (a.src != a.dst).all()


def test_kmin_draw_within_bounds():
    cfg = harness.load_config("clos64")
    mix = traffic.load("paper_grid")
    ks = traffic.param_values("dcqcn.kmin", mix["grid"]["params"]["dcqcn.kmin"], cfg, 123)
    kmax = cfg["dcqcn"]["kmax"]
    assert len(ks) == 4 and all(kmax / 4 <= k <= kmax for k in ks)
