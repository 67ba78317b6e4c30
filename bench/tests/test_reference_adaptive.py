"""The adaptive reference (``bench/reference_adaptive.py``): bitwise the
plain reference at one path and one queue a wire (on mixes with no
subnormal result), routed as the program routes, and the program on a tiny dragonfly within the dfly1056
limits, while the bfloat16 control and a broken program view are not.
On the CPU the program also holds every per-flow number to
``PER_FLOW_LIMIT``, far inside the cell's limits: a fault confined to a
few flows, such as two flows' bytes swapped, fails here."""

import dataclasses

import numpy as np
import pytest

from bench import harness, reference, traffic
from bench import reference_adaptive as ra
from bench.lookup import module
from conftest import DATA

PLAIN = {"ft64": ("ft64", DATA, "a2a_storm_tiny"),
         "clos64": ("clos64", None, "paper_grid_tiny")}
LIMITS = "dfly1056.group_shift.ugal"
#: per-flow gaps the program holds on the CPU (it reads ~1e-7)
PER_FLOW = ("delivered_gap", "rate_gap", "final_bytes_gap", "final_rate_gap")
PER_FLOW_LIMIT = 1e-5
drv = module("drivers", "sweep_routed")


@pytest.mark.parametrize("case", sorted(PLAIN))
def test_one_path_one_queue_is_the_plain_reference(case):
    name, root, mix_name = PLAIN[case]
    cfg = harness.load_config(name, root) if root else harness.load_config(name)
    mix = traffic.load(mix_name, DATA)
    runs = [harness.ref_run(cfg, s, o, f) for _, s, o, f in traffic.grid_points(mix, cfg, 5)]
    n, k = mix["n_steps"], mix["trace_every"]
    plain = reference.simulate(runs, n, k)
    adaptive = ra.simulate([ra.Run(**dataclasses.asdict(r)) for r in runs], n, k)
    for a, b in zip(plain, adaptive):
        for part in ("trace", "final"):
            for key, v in a[part].items():
                assert np.array_equal(v, b[part][key]), (part, key)


def _tiny(seed):
    cfg = harness.load_config("dfly72", DATA)
    mix = traffic.load("group_shift_tiny", DATA)
    pts = drv.points(mix, cfg, seed)
    return cfg, mix, pts, [drv.ref_point(cfg, p, seed) for p in pts]


def test_candidates_are_the_programs():
    """The reference's node paths, put in the program's link numbering,
    are the program's candidate routes, slot for slot."""
    from repro.net import make_dragonfly
    cfg, _, pts, refs = _tiny(2**31 + 9)
    fab = cfg["fabric"]
    _, idx = make_dragonfly(fab["a"], fab["p"], fab["h"])
    flows, seed = pts[0][-1], refs[0].route_seed
    pairs = list(zip(flows.src.tolist(), flows.dst.tolist()))
    dfly = module("fabrics", "dragonfly")
    routes, hops = dfly.program(fab, 0).flow_route_set(pairs, 4, seed)

    def link(a, b):
        if a[0] == "host":
            return a[1]
        if b[0] == "host":
            return idx.n_hosts + b[1]
        if a[1] == b[1]:
            return idx.local(a[1], a[2], b[2])
        return idx.gl_port(a[1], b[1])

    for f, (s, d) in enumerate(pairs):
        for k in range(4):
            nodes = dfly.path(fab, 0, s, d) if k == 0 else dfly.detour(fab, s, d, seed, k)
            ids = [link(a, b) for a, b in zip(nodes[:-1], nodes[1:])]
            assert ids == routes[f, k, :hops[f, k]].tolist(), (s, d, k)


@pytest.fixture(scope="module")
def tiny_run():
    from repro.core import Sweep
    cfg, mix, pts, refs = _tiny(11)
    res = Sweep([drv.program_point(cfg, p, 11) for p in pts]).run(
        n_steps=mix["n_steps"], trace_every=mix["trace_every"])
    return cfg, mix, pts, refs, [drv.program_view(res[i]) for i in range(len(pts))]


def test_program_within_limits(tiny_run):
    cfg, mix, pts, refs, prog = tiny_run
    gaps = drv.check(prog, refs, cfg, mix["n_steps"], mix["trace_every"])
    for name, lim in harness.load_limits(LIMITS).items():
        if name in gaps:
            assert gaps[name] <= lim, name
    nonmin = {p[2]: max(v["trace"]["n_nonmin"].max(), 0) for p, v in zip(pts, prog)}
    assert nonmin["min"] == 0 and nonmin["valiant"] == len(pts[0][-1])
    assert nonmin["ugal"] > 0


def test_control_fails():
    cfg, mix, _, refs = _tiny(12)
    n, k = mix["n_steps"], mix["trace_every"]
    good = ra.simulate(refs, n, k, "float32")
    low = ra.simulate(refs, n, k, "bfloat16")
    gaps = drv.gaps(low, good, cfg)
    assert any(gaps[name] > lim for name, lim in harness.load_limits(LIMITS).items()
               if name in gaps)


def test_swapped_routing_fails(tiny_run):
    """The program's ugal and valiant runs put in each other's place."""
    cfg, mix, pts, refs, prog = tiny_run
    modes = [p[2] for p in pts]
    swap = {"ugal": "valiant", "valiant": "ugal", "min": "min"}
    moved = [prog[i - modes.index(m) + modes.index(swap[m])] for i, m in enumerate(modes)]
    gaps = drv.check(moved, refs, cfg, mix["n_steps"], mix["trace_every"])
    assert any(gaps[name] > lim for name, lim in harness.load_limits(LIMITS).items()
               if name in gaps)


def _per_flow(gaps):
    return {name: gaps[name] for name in PER_FLOW}


def test_program_per_flow_on_the_cpu(tiny_run):
    cfg, mix, _, refs, prog = tiny_run
    gaps = drv.check(prog, refs, cfg, mix["n_steps"], mix["trace_every"])
    assert all(v <= PER_FLOW_LIMIT for v in _per_flow(gaps).values()), _per_flow(gaps)
    assert gaps["flows_apart"] == 0.0


def test_two_flows_swapped_fail_on_the_cpu(tiny_run):
    """Two flows' bytes credited to each other in one run: the per-flow
    numbers catch it, ``flows_apart`` moves by two flows."""
    cfg, mix, _, refs, prog = tiny_run
    run = prog[5]
    delivered = run["trace"]["delivered"].copy()
    delivered[:, [3, 40]] = delivered[:, [40, 3]]
    moved = list(prog)
    moved[5] = dict(trace=dict(run["trace"], delivered=delivered), final=run["final"])
    gaps = drv.check(moved, refs, cfg, mix["n_steps"], mix["trace_every"])
    assert gaps["delivered_gap"] > PER_FLOW_LIMIT
    assert 0 < gaps["flows_apart"] <= 2 / len(refs[5].src)


def test_bytes_credited_to_the_next_flow_fail(tiny_run):
    """Every run's bytes credited to the next flow: most flows are apart."""
    cfg, mix, _, refs, prog = tiny_run
    moved = [dict(trace=dict(v["trace"], delivered=np.roll(v["trace"]["delivered"], 1, axis=1)),
                  final=v["final"]) for v in prog]
    gaps = drv.check(moved, refs, cfg, mix["n_steps"], mix["trace_every"])
    assert gaps["flows_apart"] > harness.load_limits(LIMITS)["flows_apart"]
