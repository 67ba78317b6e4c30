"""``--serve`` harness: what-if query engine -> ``BENCH_serve.json``.

Replays a fixed mixed-tenant query stream (several CC stacks x several
workloads on one pod, all in one flow bucket) through ``CCQueryEngine``
and records the serving metrics:

  * latency p50 / p99 and mean micro-batch occupancy
  * executable-cache hits / misses / hit rate and the compile vs run
    wall split (the replay must compile exactly ONCE)
  * admission outcomes of a deterministic over-rate burst probe
    (fake clock: the token bucket must throttle, never queue unboundedly)

Every invocation appends a run record to ``BENCH_serve.json`` at the
repo root.  ``--quick`` shrinks the replay to CI size.

Regression gate (the CI ``serve-smoke`` job): ``check_regression``
fails on a *hit-rate collapse* (more executable builds than structural
signatures — the compile-once contract broken, e.g. a shape leaked
into the cache key) and on a p99 latency regression beyond
``(1 + TOLERANCE) x`` the committed baseline's p99, with the threshold
floored at ``ABS_FLOOR_S`` so runner-speed differences cannot flake
the gate while a recompile storm (p99 jumping by whole compile times)
still trips it.
"""

from __future__ import annotations

import json
import os
import time

BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_serve.json")

#: fail check_regression when p99 exceeds (1 + TOLERANCE) x baseline p99
TOLERANCE = 0.20

#: the p99 threshold never drops below this many seconds: CI runners are
#: slower than the baseline machine, and a warm micro-batch is far under
#: this; only a recompile storm (every batch paying ~seconds of XLA
#: compilation) or a genuine serving collapse can cross it.
ABS_FLOOR_S = 15.0

N_QUERIES = 96
N_QUERIES_QUICK = 48
N_STEPS = 400
N_STEPS_QUICK = 240
DRAIN_EVERY = 24          # queries per drain wave (a service's cadence)


def _mix():
    """(label, cfg, spec) combos: 4 CC stacks x 3 workloads, one flow
    bucket (8) on the default pod."""
    import dataclasses
    from repro.core import CCSpec, ScenarioSpec
    cfgs = {
        "rev": CCSpec(),
        "dcqcn": CCSpec(marking="cp", notification="np", reaction="rp"),
        "swift": CCSpec(reaction="swift"),
        "rev-tuned": CCSpec().replace(rev=dataclasses.replace(
            CCSpec().rev, erp_settle=0.9)),
    }
    specs = {"in4": ScenarioSpec.incast(4), "in6": ScenarioSpec.incast(6),
             "in7": ScenarioSpec.incast(7)}
    return [(f"{cn}/{sn}", cfg, spec)
            for cn, cfg in cfgs.items() for sn, spec in specs.items()]


def run_replay(quick: bool = False) -> dict:
    """The replay: returns the BENCH_serve run record."""
    from repro.serve.whatif import (AdmissionConfig, Admitted,
                                    CCQueryEngine, EngineConfig,
                                    Throttled, WhatIfQuery)

    n_queries = N_QUERIES_QUICK if quick else N_QUERIES
    n_steps = N_STEPS_QUICK if quick else N_STEPS
    mix = _mix()
    eng = CCQueryEngine(EngineConfig(
        max_batch=8, admission=AdmissionConfig(rate=1e9, burst=10_000,
                                               max_queue=256)))
    t0 = time.perf_counter()
    for i in range(n_queries):
        label, cfg, spec = mix[i % len(mix)]
        out = eng.submit(WhatIfQuery(cfg=cfg, scenario=spec,
                                     n_steps=n_steps, label=label,
                                     tenant=f"t{i % 4}"))
        assert isinstance(out, Admitted), out
        if (i + 1) % DRAIN_EVERY == 0:
            eng.drain()
    eng.drain()
    wall = time.perf_counter() - t0
    m = eng.metrics()

    # deterministic over-rate burst probe (fake clock, no jit)
    clk = [0.0]
    probe = CCQueryEngine(EngineConfig(admission=AdmissionConfig(
        rate=10.0, burst=4, max_queue=8)), clock=lambda: clk[0])
    burst = [probe.submit(WhatIfQuery(cfg=mix[0][1], scenario=mix[0][2],
                                      n_steps=n_steps))
             for _ in range(16)]
    throttle = {
        "submitted": len(burst),
        "admitted": sum(isinstance(o, Admitted) for o in burst),
        "throttled": sum(isinstance(o, Throttled) for o in burst),
        "queue_full": probe.metrics()["admission"]["queue_full"],
    }

    print(f"serve: {n_queries} queries in {wall:.1f}s "
          f"(p50={m['latency_s']['p50']:.2f}s "
          f"p99={m['latency_s']['p99']:.2f}s "
          f"occupancy={m['mean_occupancy']:.2f} "
          f"cache {m['exec_cache']['hits']}h/"
          f"{m['exec_cache']['misses']}m "
          f"compile={m['compile_s']:.1f}s run={m['run_s']:.1f}s); "
          f"burst probe: {throttle['throttled']} throttled")
    try:
        from ._env import bench_env
    except ImportError:              # `python benchmarks/serve_bench.py`
        from _env import bench_env
    return {
        "unix_time": int(time.time()),
        **bench_env(),
        "quick": quick,
        "n_queries": n_queries,
        "n_steps": n_steps,
        "wall_s": round(wall, 2),
        "metrics": m,
        "throttle_probe": throttle,
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
    print(f"appended serve record -> {path} ({len(doc['runs'])} runs)")


def check_regression(record: dict, baseline: dict | None = None,
                     tolerance: float = TOLERANCE) -> list[str]:
    """Failures when ``record`` breaks the serving contracts.

    ``baseline`` defaults to the *first* run in the committed
    BENCH_serve.json (the frozen reference).
    """
    fails = []
    m = record["metrics"]

    # compile-once / hit-rate collapse: one executable build per
    # structural signature, machine-independent and deterministic
    if m["exec_cache"]["misses"] > m["signatures"]:
        fails.append(
            f"hit-rate collapse: {m['exec_cache']['misses']} executable "
            f"builds for {m['signatures']} structural signature(s) — "
            f"a shape or content leaked into the cache key")
    if m["exec_cache"]["hit_rate"] < 0.5:
        fails.append(f"cache hit rate {m['exec_cache']['hit_rate']:.2f} "
                     f"< 0.50 across the replay")

    # explicit back-pressure: the burst probe must throttle
    probe = record["throttle_probe"]
    if probe["throttled"] == 0:
        fails.append("over-rate burst was never throttled — token "
                     "bucket not enforcing the admission rate")
    if probe["admitted"] + probe["throttled"] + probe["queue_full"] \
            != probe["submitted"]:
        fails.append("burst outcomes don't partition submissions — a "
                     "query was silently dropped or double-counted")

    # p99 latency vs the committed baseline (floored, see ABS_FLOOR_S)
    if baseline is None:
        runs = load_bench().get("runs", [])
        baseline = runs[0] if runs else None
    if baseline is None:
        fails.append("no committed BENCH_serve.json baseline")
        return fails
    base_p99 = baseline["metrics"]["latency_s"]["p99"]
    ceil = max((1.0 + tolerance) * base_p99, ABS_FLOOR_S)
    p99 = m["latency_s"]["p99"]
    if p99 > ceil:
        fails.append(
            f"p99 latency {p99:.2f}s > {ceil:.2f}s (baseline "
            f"{base_p99:.2f}s + {tolerance:.0%}, floored at "
            f"{ABS_FLOOR_S:.0f}s)")
    return fails


def main(quick: bool = False, check: bool = False) -> list[tuple]:
    """run.py section hook: replay, append, optionally gate."""
    record = run_replay(quick=quick)
    fails = check_regression(record) if check else []
    append_bench_record(record)
    m = record["metrics"]
    rows = [
        ("serve.p50_latency", m["latency_s"]["p50"] * 1e6,
         f"{m['latency_s']['p50']:.3f}s"),
        ("serve.p99_latency", m["latency_s"]["p99"] * 1e6,
         f"{m['latency_s']['p99']:.3f}s"),
        ("serve.occupancy", 0.0, f"{m['mean_occupancy']:.2f}"),
        ("serve.cache", 0.0,
         f"{m['exec_cache']['hits']}h/{m['exec_cache']['misses']}m "
         f"hit_rate={m['exec_cache']['hit_rate']:.2f}"),
        ("serve.compile_vs_run", 0.0,
         f"compile={m['compile_s']:.1f}s run={m['run_s']:.1f}s"),
        ("serve.throttled", 0.0,
         str(record["throttle_probe"]["throttled"])),
    ]
    for f in fails:
        rows.append(("serve.REGRESSION", 0.0, f))
    return rows


if __name__ == "__main__":
    import sys
    rows = main(quick="--quick" in sys.argv, check="--check" in sys.argv)
    for row in rows:
        print(",".join(str(x) for x in row))
    if any("REGRESSION" in r[0] for r in rows):
        raise SystemExit(1)
