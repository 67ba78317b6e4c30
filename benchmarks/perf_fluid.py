"""``--perf`` harness: fluid hot-loop throughput -> ``BENCH_fluid.json``.

Measures the one-pass pipeline against the pre-PR scatter path on an
F/L scaling curve of single-device grid points:

  * steps/sec of the jitted decimating scan, per reduction engine
    (``scat`` = legacy scatter baseline, ``fused`` = sorted-incidence
    one-pass reduction with the dense-CSR tiles when load skew allows)
    plus the ``mega`` whole-step kernel (one launch per trace window,
    interpret mode on a CPU backend only)
  * compile seconds per engine (first call minus steady state)
  * incidence shape per point (F, L, K, H, rows = N = F*K*H,
    ``dense_rows`` = max per-link contributors)
  * ``ops_per_step`` — jaxpr equations per substep for the fused
    reference vs the megakernel block, and their ratio
    (``op_reduction``).  On CPU the megakernel runs in interpreter
    mode, so its *wall clock* does not show the launch fusion; the op
    count is the machine-independent form of "one launch instead of a
    few hundred ops per substep", and it is what the mega gate checks
    (``op_reduction`` must hold >= MEGA_OP_REDUCTION_FLOOR and not
    regress > TOLERANCE vs the committed baseline).

Every invocation appends a run record to ``BENCH_fluid.json`` at the
repo root — the perf trajectory the ROADMAP's "fast as the hardware
allows" goal is tracked by.  ``--quick`` shrinks the grid to CI size.

Regression gate (the CI ``perf-smoke`` job): ``check_regression``
compares the *speedup ratio* (fused vs scat measured in the same
process, same machine) of the latest run against the committed
baseline's matching points.  Absolute steps/sec vary wildly across CI
runners, so the machine-normalised ratio is the stable signal; the
job fails when a point's ratio falls below ``(1 - TOLERANCE)`` x its
baseline, with that floor capped at ``FLOOR_CAP`` so cross-runner
scatter/segment-sum lowering differences cannot flake the gate while
a genuine collapse of the fused pipeline still trips it.
"""

from __future__ import annotations

import json
import os
import time

BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_fluid.json")

#: fail check_regression when a point's fused/scat speedup falls below
#: (1 - TOLERANCE) x the committed baseline's speedup for that point
TOLERANCE = 0.20

#: CI runners differ from the machine that recorded the baseline (CPU
#: model, XLA version), so a baseline-derived floor is capped here: the
#: gate catches a real collapse of the fused pipeline (back toward the
#: scatter path's throughput) without flaking on runner-to-runner
#: scatter/segment-sum lowering differences.
FLOOR_CAP = 2.0

#: the megakernel must fold at least this many jaxpr equations per
#: substep into its single launch (the acceptance bar is 5x; the
#: measured reduction is ~100x, so this is a collapse detector)
MEGA_OP_REDUCTION_FLOOR = 5.0

N_STEPS = 400
N_STEPS_QUICK = 200


def _grid(quick: bool):
    """(name, ScenarioSpec) F/L scaling curve, smallest first."""
    from repro.core import ScenarioSpec
    from repro.net import FabricSpec
    points = [
        ("clos64_f64",
         ScenarioSpec.permutation(64, seed=0, fabric=FabricSpec.clos3(4))),
        ("ft64_f1024",
         ScenarioSpec.permutation(1024, seed=0,
                                  fabric=FabricSpec.fat_tree(4, taper=1))),
    ]
    if not quick:
        points += [
            ("dfly272_f1024_k4",
             ScenarioSpec.permutation(
                 1024, seed=0, fabric=FabricSpec.dragonfly(4, 4, 4),
                 n_paths=4, route_seed=0)),
            ("dfly272_f4096",
             ScenarioSpec.permutation(
                 4096, seed=0, fabric=FabricSpec.dragonfly(4, 4, 4))),
        ]
    return points


def _interpret() -> bool:
    """Interpret mode for the mega cells: on a CPU backend only."""
    try:
        from ._env import pallas_interpret
    except ImportError:              # `python benchmarks/perf_fluid.py`
        from _env import pallas_interpret
    return pallas_interpret()


def _bench_point(spec, n_steps: int, engine: str) -> dict:
    import jax
    from repro.core import PAPER_CONFIG
    from repro.core.fluid import init_state, make_step_fn
    from repro.core.simulator import decimating_scan, make_block_fn

    cfg = PAPER_CONFIG
    scn = spec.build(cfg)
    st0 = init_state(scn, cfg)
    k = 10
    if engine == "mega":
        block = make_block_fn(scn, cfg, k, interpret=_interpret())
        fn = jax.jit(lambda st: decimating_scan(
            None, st, n_steps // k, k, cfg.sim.dt, block_fn=block))
    else:
        step = make_step_fn(scn, cfg, reduce=engine)
        fn = jax.jit(lambda st: decimating_scan(step, st, n_steps // k, k,
                                                cfg.sim.dt))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(st0))
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(st0))
        best = min(best, time.perf_counter() - t0)
    return {"steps_per_s": round(n_steps / best, 1),
            "compile_s": round(compile_s - best, 2)}


def _ops_per_step(spec, k: int = 10) -> dict:
    """Jaxpr equations per substep: fused reference vs megakernel block.

    The fused step traces to a few hundred equations, each an XLA op
    (and on TPU, one or more kernel launches); the megakernel block is
    a single ``pallas_call`` equation covering ``k`` substeps.  The
    ratio is the machine-independent measure of the launch fusion —
    wall-clock on the CPU interpret path cannot show it.
    """
    import jax
    from repro.core import PAPER_CONFIG
    from repro.core.fluid import init_state, make_step_fn
    from repro.core.simulator import make_block_fn

    cfg = PAPER_CONFIG
    scn = spec.build(cfg)
    st0 = init_state(scn, cfg)
    step = make_step_fn(scn, cfg)
    ref_eqns = len(jax.make_jaxpr(step)(st0).eqns)
    block = make_block_fn(scn, cfg, k, interpret=_interpret())
    blk_eqns = len(jax.make_jaxpr(block)(st0).eqns)
    return {"ref": ref_eqns, "mega_block": blk_eqns,
            "mega": round(blk_eqns / k, 2),
            "reduction": round(ref_eqns / (blk_eqns / k), 1)}


def run_perf(quick: bool = False) -> dict:
    """Execute the grid; returns the BENCH_fluid run record."""
    import jax
    from repro.core import PAPER_CONFIG
    from repro.core.fluid import dense_reduce_rows

    n_steps = N_STEPS_QUICK if quick else N_STEPS
    points = []
    for name, spec in _grid(quick):
        scn = spec.build(PAPER_CONFIG)
        F, H = scn.routes.shape
        K = 1 if scn.alt_routes is None else scn.alt_routes.shape[1]
        rec = {
            "name": name,
            "F": F, "H": H, "K": K,
            "L": int(scn.capacity.shape[0]),
            "rows": F * K * H,
            "dense_rows": dense_reduce_rows(scn),
            "steps": n_steps,
        }
        for engine in ("scat", "fused", "mega"):
            rec[engine] = _bench_point(spec, n_steps, engine)
        rec["speedup"] = round(
            rec["fused"]["steps_per_s"] / rec["scat"]["steps_per_s"], 2)
        # interpret-mode wall clock, recorded honestly (CPU pays the
        # interpreter; the launch fusion shows in ops_per_step)
        rec["mega_speedup"] = round(
            rec["mega"]["steps_per_s"] / rec["fused"]["steps_per_s"], 2)
        rec["ops_per_step"] = _ops_per_step(spec)
        points.append(rec)
        print(f"perf.{name}: scat={rec['scat']['steps_per_s']:.0f}/s "
              f"fused={rec['fused']['steps_per_s']:.0f}/s "
              f"speedup={rec['speedup']:.2f}x "
              f"mega={rec['mega']['steps_per_s']:.0f}/s "
              f"ops/step {rec['ops_per_step']['ref']}->"
              f"{rec['ops_per_step']['mega']:g} "
              f"({rec['ops_per_step']['reduction']:.0f}x fewer) "
              f"(F={F} L={rec['L']} K={K} dense_rows={rec['dense_rows']})")
    try:
        from ._env import bench_env
    except ImportError:              # `python benchmarks/perf_fluid.py`
        from _env import bench_env
    return {
        "unix_time": int(time.time()),
        # the mega cells' mode; the scat/fused cells never interpret
        **bench_env(interpret=_interpret()),
        "quick": quick,
        "points": points,
    }


def load_bench(path: str = BENCH_PATH) -> dict:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {"runs": []}


def append_bench_record(record: dict, path: str = BENCH_PATH) -> None:
    doc = load_bench(path)
    doc.setdefault("runs", []).append(record)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"appended perf record -> {path} ({len(doc['runs'])} runs)")


def check_regression(record: dict, baseline: dict | None = None,
                     tolerance: float = TOLERANCE) -> list[str]:
    """Failures when ``record``'s speedups regress vs the baseline run.

    ``baseline`` defaults to the *first* run in the committed
    BENCH_fluid.json (the frozen reference); points are matched by
    name, unmatched points are skipped (the quick grid is a subset).
    """
    if baseline is None:
        runs = load_bench().get("runs", [])
        if not runs:
            return ["no committed BENCH_fluid.json baseline"]
        baseline = runs[0]
    base = {p["name"]: p for p in baseline["points"]}
    fails = []
    for p in record["points"]:
        b = base.get(p["name"])
        if b is None:
            continue
        floor = min((1.0 - tolerance) * b["speedup"], FLOOR_CAP)
        if p["speedup"] < floor:
            fails.append(
                f"{p['name']}: fused/scat speedup {p['speedup']:.2f}x "
                f"< {floor:.2f}x (baseline {b['speedup']:.2f}x "
                f"- {tolerance:.0%}, capped at {FLOOR_CAP:.1f}x)")
        # megakernel gate: the per-substep op reduction (the launch
        # fusion, machine-independent) must hold the absolute floor
        # and stay within TOLERANCE of the committed baseline's
        ops = p.get("ops_per_step")
        if ops is None:
            continue
        mega_floor = MEGA_OP_REDUCTION_FLOOR
        if b.get("ops_per_step"):
            mega_floor = max(mega_floor, (1.0 - tolerance) *
                             b["ops_per_step"]["reduction"])
        if ops["reduction"] < mega_floor:
            fails.append(
                f"{p['name']}: megakernel op reduction "
                f"{ops['reduction']:.1f}x < {mega_floor:.1f}x "
                f"(ref {ops['ref']} eqns/step vs mega "
                f"{ops['mega']:g}; floor {MEGA_OP_REDUCTION_FLOOR:.0f}x"
                f" abs / baseline - {tolerance:.0%})")
    return fails


def main(quick: bool = False, check: bool = False) -> list[tuple]:
    """run.py section hook: bench, append, optionally gate."""
    record = run_perf(quick=quick)
    fails = check_regression(record) if check else []
    append_bench_record(record)
    rows = []
    for p in record["points"]:
        rows.append((f"perf_fluid.{p['name']}",
                     1e6 / p["fused"]["steps_per_s"],
                     f"fused={p['fused']['steps_per_s']:.0f}/s "
                     f"speedup={p['speedup']:.2f}x "
                     f"mega_ops {p['ops_per_step']['ref']}->"
                     f"{p['ops_per_step']['mega']:g}/step "
                     f"({p['ops_per_step']['reduction']:.0f}x)"))
    for f in fails:
        rows.append(("perf_fluid.REGRESSION", 0.0, f))
    return rows


if __name__ == "__main__":
    import sys
    rows = main(quick="--quick" in sys.argv, check="--check" in sys.argv)
    for row in rows:
        print(",".join(str(x) for x in row))
    if any("REGRESSION" in r[0] for r in rows):
        raise SystemExit(1)
