"""``--cc-matrix`` harness: the full stage-combination sweep.

Enumerates the ``repro.core.cc`` registries — every (marking x
notification x reaction) combination, including variants registered
after this file was written — and runs the whole matrix on the paper's
incast scene as ONE ``Sweep`` launch.  The stage selectors are traced
data, so the matrix shares a single compiled step; the harness asserts
that (``SWEEP_EXEC_CACHE`` must report exactly one executable build) and
appends the per-combination headline rows to ``BENCH_fluid.json``
under the ``cc_matrix`` key (the CI ``cc-matrix`` job uploads the
refreshed file as an artifact).
"""

from __future__ import annotations

import time

N_STEPS = 4000
N_STEPS_QUICK = 1200


def run_matrix(quick: bool = False) -> dict:
    """Execute the registry product; returns the BENCH record."""
    from repro.core import CCSpec, ScenarioSpec, Sweep, cc
    from repro.core.experiments import SWEEP_EXEC_CACHE
    try:
        from ._env import bench_env
    except ImportError:              # `python benchmarks/cc_matrix.py`
        from _env import bench_env

    from repro.core import DCQCNParams, SimParams

    # give the new variants a regime where they are *distinct*: a real
    # kmin < kmax ramp for slope marking (the defaults' kmin == kmax
    # degenerates it to step marking), and a 0.25 us integrator so the
    # CNP feedback delay spans ~9 steps and FNCC's in-path shortcut is
    # observable (at dt = 1 us the whole RTT rounds to the 2-step floor)
    base = CCSpec(
        dcqcn=DCQCNParams(kmax=4 * 15 * 1024.0, pmax=0.25),
        sim=SimParams(dt=0.25e-6))
    configs = {
        f"{m}+{n}+{r}": base.replace(marking=m, notification=n,
                                     reaction=r)
        for m in cc.MARKING.names()
        for n in cc.NOTIFICATION.names()
        for r in cc.REACTION.names()
    }
    # the paper scene, opened early so even the quick run covers the
    # congestion transient (default generators open at 1 ms)
    scn = ScenarioSpec.paper_incast(roll=0, t_start=0.1e-3,
                                    label="hol")
    n_steps = (N_STEPS_QUICK if quick else N_STEPS) * 4
    misses0 = SWEEP_EXEC_CACHE.stats().misses
    t0 = time.perf_counter()
    res = Sweep.grid(configs=configs, scenarios={"hol": scn}).run(
        n_steps=n_steps)
    wall = time.perf_counter() - t0
    compiles = SWEEP_EXEC_CACHE.stats().misses - misses0
    points = []
    for name, row in res.summary().items():
        points.append({
            "name": name,
            "aggregate_gbps": round(row["aggregate_gbps"], 3),
            "min_flow_gbps": round(row["min_flow_gbps"], 3),
            "peak_queue_kb": round(row["peak_queue_kb"], 1),
            "marks": row["marks"],
            "cnps": row["cnps"],
        })
    return {
        "unix_time": int(time.time()),
        **bench_env(),
        "quick": quick,
        "n_steps": n_steps,
        "n_points": len(points),
        "compiles": compiles,
        "wall_s": round(wall, 2),
        "marking": list(cc.MARKING.names()),
        "notification": list(cc.NOTIFICATION.names()),
        "reaction": list(cc.REACTION.names()),
        "points": points,
    }


def _perf_fluid():
    """The sibling module owning BENCH_fluid.json (both import modes)."""
    try:
        from . import perf_fluid
    except ImportError:              # `python benchmarks/cc_matrix.py`
        import perf_fluid
    return perf_fluid


def append_matrix_record(record: dict) -> None:
    import json

    pf = _perf_fluid()
    doc = pf.load_bench()
    doc.setdefault("cc_matrix", []).append(record)
    with open(pf.BENCH_PATH, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"appended cc-matrix record -> {pf.BENCH_PATH} "
          f"({len(doc['cc_matrix'])} records)")


def main(quick: bool = False) -> list[tuple]:
    """run.py section hook: run the matrix, append, sanity-gate."""
    record = run_matrix(quick=quick)
    append_matrix_record(record)
    rows = []
    for p in record["points"]:
        rows.append((f"cc_matrix.{p['name']}", 0.0,
                     f"agg={p['aggregate_gbps']:.2f}GB/s "
                     f"min={p['min_flow_gbps']:.2f}GB/s "
                     f"marks={p['marks']} cnps={p['cnps']}"))
    if record["compiles"] != 1:
        rows.append(("cc_matrix.RECOMPILE", 0.0,
                     f"{record['n_points']} stage combinations took "
                     f"{record['compiles']} executable builds; the "
                     f"matrix must ride ONE jit"))
    else:
        rows.append(("cc_matrix.one_launch", record["wall_s"] * 1e6,
                     f"{record['n_points']} combos, 1 compile, "
                     f"{record['wall_s']:.1f}s"))
    return rows


if __name__ == "__main__":
    import sys
    rows = main(quick="--quick" in sys.argv)
    for row in rows:
        print(",".join(str(x) for x in row))
    if any("RECOMPILE" in r[0] for r in rows):
        raise SystemExit(1)
