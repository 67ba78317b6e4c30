"""The plain reference against the program, and the control against the
reference, at sizes a test run holds (the chip readings are in PERF.md).

The program's run at the stated precision (float32) must come inside
every limit; the reference computed in bfloat16, put in the program's
place, must not."""

import numpy as np
import pytest

from bench import harness, reference, traffic
from bench.lookup import module
from conftest import DATA

CASES = {"ft64": ("ft64", DATA, "a2a_storm_tiny", "ft1000.a2a_storm.sweep"),
         "clos64": ("clos64", None, "paper_grid_tiny", "clos64.paper_grid.sweep")}


def _points(case, seed):
    cfg_name, cfg_root, mix_name, _ = CASES[case]
    cfg = harness.load_config(cfg_name, cfg_root) if cfg_root else harness.load_config(cfg_name)
    mix = traffic.load(mix_name, DATA)
    return cfg, mix, traffic.grid_points(mix, cfg, seed)


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_within_limits(case):
    from repro.core import Sweep
    cfg, mix, pts = _points(case, 11)
    n, k = mix["n_steps"], mix["trace_every"]
    res = Sweep([(name, harness.cc_spec(cfg, s, o), harness.scenario_spec(cfg, f))
                 for name, s, o, f in pts]).run(n_steps=n, trace_every=k)
    prog = [harness.program_view(res[i]) for i in range(len(pts))]
    refs = [harness.ref_run(cfg, s, o, f) for _, s, o, f in pts]
    gaps = harness.reference_check(prog, refs, cfg, n, k)
    for name, lim in harness.load_limits(CASES[case][3]).items():
        if name in gaps:
            assert gaps[name] <= lim, name


@pytest.mark.parametrize("case", sorted(CASES))
def test_control_fails(case):
    cfg, mix, pts = _points(case, 12)
    n, k = mix["n_steps"], mix["trace_every"]
    refs = [harness.ref_run(cfg, s, o, f) for _, s, o, f in pts]
    good = reference.simulate(refs, n, k, "float32")
    low = reference.simulate(refs, n, k, "bfloat16")
    limits = harness.load_limits(CASES[case][3])
    gaps = harness.compare(low, good, cfg)
    assert any(gaps[name] > lim for name, lim in limits.items() if name in gaps)


def test_xgft_paths_are_dmodk():
    """Up ports follow the destination's digits; roll 1 swaps the two
    upper selectors (the paper's wire-disjoint wiring)."""
    path = module("fabrics", "xgft").path
    fab = {"kind": "xgft", "m": [4, 4, 4], "w": [1, 4, 4]}
    p0 = path(fab, 0, 0, 16 + 2)
    p1 = path(fab, 1, 0, 16 + 2)
    assert len(p0) == 7 and p0[0] == ("host", 0) and p0[-1] == ("host", 18)
    assert p0[2][1] == (0, 2) and p0[3][1] == (0, 2, 0)
    assert p1[2][1] == (0, 0) and p1[3][1] == (0, 0, 2)
    assert len(path(fab, 0, 0, 1)) == 3


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_routes_as_the_program(case):
    """The fabric module's reference path and the program's route table
    give every flow the same number of hops."""
    cfg, mix, pts = _points(case, 13)
    for _, scheme, over, flows in pts[:1]:
        scn = harness.scenario_spec(cfg, flows).build(harness.cc_spec(cfg, scheme, over))
        prog_hops = (np.asarray(scn.routes) >= 0).sum(axis=1)[:len(flows)]
        net = reference.Network([harness.ref_run(cfg, scheme, over, flows)])
        assert np.array_equal(prog_hops, net.hops)
