"""Adaptive non-minimal routing: RouteSet properties, Valiant/VLB
structure, UGAL parity with single-path runs, and the routing axis in
one Sweep launch."""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import CCScheme, PAPER_CONFIG, ScenarioSpec, Sweep, run
from repro.core.workloads import group_shift
from repro.net import (FabricSpec, dragonfly_route_set, make_dragonfly,
                       validate_route_set)

CFG = PAPER_CONFIG


def _paths_of(rset, s, d):
    """Real link-id path of every candidate slot of pair (s, d)."""
    return [[int(x) for x in rset.paths[s, d, k, : rset.hops[s, d, k]]]
            for k in range(rset.k_paths)]


# ---------------------------------------------------------------------------
# property: dragonfly Valiant structure over (a, p, h) x seeds
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(a=st.integers(min_value=2, max_value=4),
       p=st.integers(min_value=1, max_value=2),
       h=st.integers(min_value=1, max_value=2),
       seed=st.integers(min_value=0, max_value=3))
def test_dragonfly_valiant_paths_valid_and_one_intermediate(a, p, h, seed):
    """Every candidate layer passes the structural checker, and every
    inter-group detour visits exactly one intermediate group."""
    topo, idx = make_dragonfly(a=a, p=p, h=h)
    rset = dragonfly_route_set(idx, k=3, seed=seed)
    validate_route_set(topo, rset)           # link contiguity, endpoints
    n = idx.n_hosts
    pairs = [(s, d) for s in range(0, n, max(1, n // 6))
             for d in range(1, n, max(1, n // 5)) if s != d]
    for s, d in pairs:
        gs, gd = idx.host_group(s), idx.host_group(d)
        minimal = _paths_of(rset, s, d)[0]
        for path in _paths_of(rset, s, d)[1:]:
            groups = idx.groups_visited(path)
            if path == minimal:              # no detour existed: fallback
                continue
            if gs != gd:
                mid = [g for g in groups if g not in (gs, gd)]
                assert len(mid) == 1, (s, d, path, groups)
                assert groups == [gs, mid[0], gd]
                n_global = sum(idx.is_global(lid) for lid in path)
                assert n_global == 2
            else:                            # in-group router detour
                assert groups == [gs]


@settings(max_examples=6, deadline=None)
@given(a=st.integers(min_value=2, max_value=4),
       seed=st.integers(min_value=0, max_value=2))
def test_dragonfly_valiant_flattens_global_load(a, seed):
    """Under random permutations, the Valiant candidate layers spread
    global-channel load strictly flatter (max/mean) than minimal."""
    topo, idx = make_dragonfly(a=a, p=2, h=2)
    rset = dragonfly_route_set(idx, k=4, seed=seed)
    n = idx.n_hosts
    rng = np.random.RandomState(seed + 17)
    perm = rng.permutation(n)
    pairs = [(s, int(perm[s])) for s in range(n) if perm[s] != s]
    gids = idx.global_ids()

    def ratio(load):
        sel = load[gids].astype(np.float64)
        return sel.max() / max(sel.mean(), 1e-12)

    r_min = ratio(rset.link_load(topo.n_links, pairs, k=0))
    # each flow's detour layers together: 2 sampled globals per flow
    alt = sum(rset.link_load(topo.n_links, pairs, k=j)
              for j in range(1, rset.k_paths))
    assert ratio(alt) < r_min, (ratio(alt), r_min)


def test_dragonfly_adversarial_load_provably_flatter():
    """Group-shift traffic: minimal routing puts a whole group's flows
    on ONE global channel; the Valiant layers stay within a constant
    max/mean factor while minimal is off by ~#channels."""
    topo, idx = make_dragonfly(a=4, p=2, h=2)
    rset = dragonfly_route_set(idx, k=4, seed=0)
    wl = group_shift(idx.g, idx.a * idx.p)
    pairs = list(zip(wl.src, wl.dst))
    gids = idx.global_ids()
    load_min = rset.link_load(topo.n_links, pairs, k=0)[gids]
    # minimal: g channels carry a*p flows each, the rest exactly zero
    assert load_min.max() == idx.a * idx.p
    assert (load_min > 0).sum() == idx.g
    mean_min = load_min.mean()
    alt = sum(rset.link_load(topo.n_links, pairs, k=j)
              for j in range(1, rset.k_paths))[gids]
    # VLB: every channel sees some load; max/mean bounded well below
    # minimal's (which concentrates everything on 1/#channels of links)
    assert alt.max() / alt.mean() < 0.5 * (load_min.max() / mean_min)


# ---------------------------------------------------------------------------
# property: XGFT / CLOS Valiant candidates stay valid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fab", [
    FabricSpec.clos3(4),
    FabricSpec.xgft((4, 4, 4), (1, 4, 4)),
    FabricSpec.fat_tree(4, taper=2),
    FabricSpec.xgft((2, 2, 2, 2), (1, 2, 2, 2)),
    FabricSpec.dragonfly(a=4, p=2, h=2),
    FabricSpec.dragonfly(a=2, p=2, h=1, groups=3),
], ids=lambda f: f.name)
def test_route_set_every_layer_valid(fab):
    validate_route_set(fab.build(), fab.route_set(4, seed=1))


def test_route_set_slot0_is_minimal_table():
    fab = FabricSpec.dragonfly(a=4, p=2, h=2)
    rset, table = fab.route_set(4), fab.route_table()
    np.testing.assert_array_equal(rset.hops[:, :, 0], table.hops)
    np.testing.assert_array_equal(
        rset.paths[:, :, 0, :5], table.paths)     # VLB pads H 5 -> 7
    assert (rset.paths[:, :, 0, 5:] == -1).all()


def test_route_set_cached_and_seed_keyed():
    fab = FabricSpec.dragonfly(a=2, p=2, h=1)
    assert fab.route_set(3, seed=0) is fab.route_set(3, seed=0)
    assert fab.route_set(3, seed=0) is not fab.route_set(3, seed=1)


# ---------------------------------------------------------------------------
# parity: UGAL with zero backlog == the single-path RouteTable run
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fab", [
    FabricSpec.clos3(4),
    FabricSpec.fat_tree(4, taper=2),
    FabricSpec.dragonfly(a=4, p=2, h=2),
], ids=lambda f: f.name)
def test_ugal_zero_backlog_bitexact_vs_single_path(fab):
    """Uncongested traffic (no queues at selection epochs, no CNPs):
    UGAL must pin every flow to its minimal path and reproduce the
    legacy single-path run bit for bit — traces AND final state."""
    mk = lambda **kw: ScenarioSpec.permutation(
        12, seed=3, fabric=fab, t_start=0.0,
        gen_rate=0.05 * CFG.link.line_rate, **kw)
    base = run(mk().build(CFG), CFG, n_steps=800)
    assert int(base.cnp.sum()) == 0          # scenario really is idle
    for mode in ("min", "valiant", "ugal"):
        cfg = CFG.replace(routing=mode)
        res = run(mk(n_paths=4).build(cfg), cfg, n_steps=800)
        if mode == "valiant":                # pinned detours DO diverge
            assert int(res.n_nonmin.max()) > 0
            continue
        for field in ("delivered", "rate", "inst_thr", "max_q",
                      "n_paused", "marked", "cnp"):
            np.testing.assert_array_equal(
                getattr(res, field), getattr(base, field),
                err_msg=f"{mode}/{field}")
        for field in ("nicq", "delivered", "rate"):
            np.testing.assert_array_equal(
                np.asarray(getattr(res.final, field)),
                np.asarray(getattr(base.final, field)),
                err_msg=f"{mode}/final.{field}")
        for field in ("qh", "est"):         # [F, H]: VLB pads H 5 -> 7
            a = np.asarray(getattr(res.final, field))
            b = np.asarray(getattr(base.final, field))
            np.testing.assert_array_equal(
                a[:, : b.shape[1]], b, err_msg=f"{mode}/final.{field}")
            assert (a[:, b.shape[1]:] == 0).all()
        assert int(np.asarray(res.final.path_idx).max()) == 0
        assert int(res.n_nonmin.max()) == 0


# ---------------------------------------------------------------------------
# acceptance: routing x scheme in ONE Sweep launch, UGAL wins adversarial
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def routing_sweep():
    fab = FabricSpec.dragonfly(a=4, p=2, h=2)
    wl = group_shift(9, 8, t_stop=1.5e-3)
    spec = wl.spec(fabric=fab, n_paths=4, label="adv")
    configs = {
        f"{s.name}/{r}": CFG.replace(scheme=s, routing=r)
        for s in CCScheme for r in ("min", "valiant", "ugal")}
    return Sweep.grid(configs=configs, scenarios={"adv": spec}).run(
        n_steps=1200)


@pytest.mark.parametrize("scheme", list(CCScheme))
def test_ugal_beats_minimal_on_adversarial_dragonfly(routing_sweep, scheme):
    """{min, valiant, ugal} x all schemes ride one launch; non-minimal
    routing must strictly win delivered throughput on the group-shift
    pattern that hotspots a single global channel per group."""
    res = routing_sweep
    delivered = {r: float(np.asarray(
        res[f"{scheme.name}/{r}/adv"].final.delivered).sum())
        for r in ("min", "valiant", "ugal")}
    assert delivered["ugal"] >= 1.5 * delivered["min"], delivered
    assert delivered["valiant"] >= 1.5 * delivered["min"], delivered
    # and UGAL actually moved flows off their minimal paths
    assert int(res[f"{scheme.name}/ugal/adv"].n_nonmin.max()) > 0
    assert int(res[f"{scheme.name}/min/adv"].n_nonmin.max()) == 0


def test_routing_modes_share_one_scenario_build(routing_sweep):
    """All 9 points carry the same [F, K, H] candidate tensors — the
    routing decision is config data, not scenario structure."""
    res = routing_sweep
    assert len(res) == 9
    shapes = {res[n].scn.alt_routes.shape for n in res.names}
    assert shapes == {(72, 4, 7)}
