"""Fleet fabric benchmark -> BENCH_fleet.json.

Runs one ragged scheme x scenario grid twice — the single-launch
``Sweep.run()`` reference and the threaded work-stealing fleet
(streaming + journal) — and records:

  * **scheduling overhead** — fleet wall over single-launch wall minus
    one (the price of shard launches + streaming + journaling; gated
    against the committed baseline with ``--check``),
  * **bitwise fidelity** — the merged fleet result must equal the
    reference over every trace field and the final state (recorded,
    and a hard gate),
  * **fleet health** — per-signature compile count (must be 1 for the
    envelope plan), steal/retry counters, and Abandoned shards (any is
    a hard gate).

Record schema (appended to ``runs`` in BENCH_fleet.json)::

    {unix_time, quick, backend/platform/... (bench_env), n_points,
     n_shards, n_workers, n_steps, single_wall_s, fleet_wall_s,
     overhead_frac, bitwise, compiles, stolen, retries, resumed,
     abandoned}
"""

from __future__ import annotations

import json
import os
import tempfile
import time

BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_fleet.json")

#: overhead gate: fail when overhead_frac exceeds the committed
#: baseline's by more than this (plus an absolute slack floor for
#: cross-runner noise — threaded scheduling on a busy CI box jitters).
TOLERANCE = 0.20
ABS_SLACK = 0.50

N_STEPS, N_STEPS_QUICK = 2000, 500


def _env():
    try:
        from . import _env as env_mod
    except ImportError:              # `python benchmarks/fleet_bench.py`
        import _env as env_mod
    return env_mod


def _grid(quick: bool):
    """A deliberately ragged grid: mixed flow counts so the LPT plan
    has something to balance and the stealers something to steal."""
    from repro.core import CCScheme, PAPER_CONFIG, ScenarioSpec, Sweep

    schemes = [CCScheme.DCQCN, CCScheme.DCQCN_REV] if quick \
        else list(CCScheme)
    scns = {"i2": ScenarioSpec.incast(2, victim=False),
            "i6": ScenarioSpec.incast(6, victim=False),
            "hol": ScenarioSpec.paper_incast(roll=0)}
    if not quick:
        scns["i12"] = ScenarioSpec.incast(12, victim=False)
    return Sweep.grid(
        configs={s.name: PAPER_CONFIG.replace(scheme=s)
                 for s in schemes},
        scenarios=scns)


def _bitwise(fleet_res, ref) -> bool:
    import jax
    import numpy as np
    from repro.core.serialize import _SIM_TRACE_FIELDS

    if not np.array_equal(fleet_res.times, ref.times):
        return False
    for f in _SIM_TRACE_FIELDS:
        a = getattr(fleet_res.traces, f)
        b = getattr(ref.traces, f)
        if (a is None) != (b is None):
            return False
        if a is not None and not np.array_equal(np.asarray(a),
                                                np.asarray(b)):
            return False
    la = jax.tree.flatten(fleet_res.final)[0]
    lb = jax.tree.flatten(ref.final)[0]
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def run_fleet_bench(quick: bool = False) -> dict:
    from repro.fleet import FleetConfig, run_fleet

    sweep = _grid(quick)
    n_steps = N_STEPS_QUICK if quick else N_STEPS
    trace_every = n_steps // 10

    # single-launch reference (warms the shared executable cache for
    # neither side: the fleet pads to the same envelope, so both pay
    # exactly one compile of the same program — time them separately)
    t0 = time.perf_counter()
    ref = sweep.run(n_steps=n_steps, trace_every=trace_every)
    single_wall = time.perf_counter() - t0

    cfg = FleetConfig(n_workers=3, max_points=2)
    with tempfile.TemporaryDirectory(prefix="fleet_bench_") as d:
        t0 = time.perf_counter()
        out = run_fleet(sweep, n_steps, trace_every, config=cfg,
                        journal=d)
        fleet_wall = time.perf_counter() - t0

    s = out.stats
    record = {
        "unix_time": int(time.time()),
        "quick": quick,
        **_env().bench_env(),
        "n_points": len(sweep.points),
        "n_shards": s.n_shards,
        "n_workers": cfg.n_workers,
        "n_steps": n_steps,
        "single_wall_s": round(single_wall, 3),
        "fleet_wall_s": round(fleet_wall, 3),
        "overhead_frac": round(fleet_wall / single_wall - 1.0, 3),
        "bitwise": _bitwise(out.result, ref),
        "compiles": s.compiles,
        "stolen": s.stolen,
        "retries": s.retries,
        "resumed": s.resumed,
        "abandoned": s.abandoned,
    }
    print(f"fleet: {record['n_points']} pts / {record['n_shards']} "
          f"shards / {cfg.n_workers} workers: single "
          f"{single_wall:.2f}s fleet {fleet_wall:.2f}s "
          f"(overhead {record['overhead_frac']:+.1%}), "
          f"bitwise={record['bitwise']} compiles={s.compiles} "
          f"stolen={s.stolen} abandoned={s.abandoned}")
    return record


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
    print(f"appended fleet record -> {path} ({len(doc['runs'])} runs)")


def check_regression(record: dict,
                     baseline: dict | None = None) -> list[str]:
    """Hard gates (always-on facts) + the overhead gate vs the first
    committed BENCH_fleet.json run."""
    fails = []
    if not record["bitwise"]:
        fails.append("fleet result is NOT bitwise the single-launch "
                     "Sweep.run() reference")
    if record["abandoned"]:
        fails.append(f"{record['abandoned']} shard(s) abandoned")
    if record["compiles"] > 1:
        fails.append(f"envelope plan compiled {record['compiles']}x "
                     f"(must share ONE executable)")
    if baseline is None:
        runs = load_bench().get("runs", [])
        if not runs:
            return fails + ["no committed BENCH_fleet.json baseline"]
        baseline = runs[0]
    ceiling = baseline["overhead_frac"] + TOLERANCE + ABS_SLACK
    if record["overhead_frac"] > ceiling:
        fails.append(
            f"scheduling overhead {record['overhead_frac']:+.1%} > "
            f"{ceiling:+.1%} (baseline "
            f"{baseline['overhead_frac']:+.1%} + {TOLERANCE:.0%} "
            f"+ {ABS_SLACK:.0%} slack)")
    return fails


def main(quick: bool = False, check: bool = False) -> list[tuple]:
    """run.py section hook: bench, append, optionally gate."""
    record = run_fleet_bench(quick=quick)
    fails = check_regression(record) if check else []
    append_bench_record(record)
    rows = [
        ("fleet.single_wall", record["single_wall_s"] * 1e6,
         f"{record['single_wall_s']:.2f}s one launch"),
        ("fleet.fleet_wall", record["fleet_wall_s"] * 1e6,
         f"{record['fleet_wall_s']:.2f}s {record['n_shards']} shards "
         f"x {record['n_workers']} workers "
         f"(overhead {record['overhead_frac']:+.1%})"),
        ("fleet.bitwise", 0.0, str(record["bitwise"])),
        ("fleet.compiles", 0.0, str(record["compiles"])),
        ("fleet.stolen", 0.0, str(record["stolen"])),
        ("fleet.abandoned", 0.0, str(record["abandoned"])),
    ]
    for f in fails:
        rows.append(("fleet.REGRESSION", 0.0, f))
    return rows


if __name__ == "__main__":
    import sys
    rows = main(quick="--quick" in sys.argv, check="--check" in sys.argv)
    for row in rows:
        print(",".join(str(x) for x in row))
    if any("REGRESSION" in r[0] for r in rows):
        raise SystemExit(1)
