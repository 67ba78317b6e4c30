"""Per-pair candidate builds: ``FabricSpec.flow_route_set`` builds and
validates only a scenario's pairs, and is bitwise the full ``RouteSet``
sliced to them."""

import numpy as np
import pytest

from repro.core import obs
from repro.net import FabricSpec, validate_pair_routes

FABRICS = [FabricSpec.clos3(4),
           FabricSpec.xgft((4, 2, 2), (1, 2, 2)),
           FabricSpec.dragonfly(a=4, p=2, h=2)]
SEEDS = [0, 7, 2**31 + 5]


def _pairs(n: int, seed: int) -> list:
    """Random pairs with a self pair and a repeat among them."""
    rng = np.random.default_rng(seed % 2**32)
    pairs = [tuple(int(v) for v in rng.integers(0, n, 2)) for _ in range(40)]
    return pairs + [(3, 3), pairs[0]]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("fab", FABRICS, ids=lambda f: f.name)
def test_pairs_equal_full_set_sliced(fab, k, seed):
    pairs = _pairs(fab.n_nodes, seed)
    paths, hops = fab.flow_route_set(pairs, k, seed=seed)
    full = fab.route_set(k, seed=seed)
    idx = np.asarray(pairs)
    assert paths.dtype == full.paths.dtype and hops.dtype == full.hops.dtype
    np.testing.assert_array_equal(paths, full.paths[idx[:, 0], idx[:, 1]])
    np.testing.assert_array_equal(hops, full.hops[idx[:, 0], idx[:, 1]])
    assert not paths.flags.writeable


def _break_start(paths, hops):
    paths[0, 1, 0] = paths[1, 1, 0] if paths[1, 1, 0] != paths[0, 1, 0] \
        else paths[2, 1, 0]


def _break_chain(paths, hops):
    paths[0, 1, 1], paths[0, 1, 2] = paths[0, 1, 2], paths[0, 1, 1]


def _break_padding(paths, hops):
    paths[0, 1, hops[0, 1] - 2] = -1


@pytest.mark.parametrize("fault, message", [
    (_break_start, "does not start at its source host"),
    (_break_chain, "sinks at"),
    (_break_padding, "non-trailing PAD"),
], ids=["start", "chain", "padding"])
def test_bad_path_refused(fault, message):
    fab = FabricSpec.dragonfly(a=4, p=2, h=2)
    pairs = [(0, 70), (9, 40), (17, 33)]
    paths, hops = (np.array(x) for x in fab.flow_route_set(pairs, 4, seed=3))
    validate_pair_routes(fab.build(), pairs, paths, hops)
    fault(paths, hops)
    with pytest.raises(AssertionError, match=f"candidate layer 1: .*{message}"):
        validate_pair_routes(fab.build(), pairs, paths, hops)


@pytest.mark.parametrize("fab", FABRICS, ids=lambda f: f.name)
def test_paths_built_counted(fab):
    pairs = _pairs(fab.n_nodes, 11)
    before = obs.stats()
    fab.flow_route_set(pairs, 3, seed=123457)        # a key no test shares
    fab.flow_route_set(pairs, 3, seed=123457)        # cached: builds nothing
    window = obs.stats() - before
    n_real = sum(s != d for s, d in pairs)
    assert window.counts["routes.paths_built"] == 3 * n_real
    assert window.span("repro.routes.build").n == 1
