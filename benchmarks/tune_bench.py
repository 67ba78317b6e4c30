"""``--tune`` harness: autotuning run -> ``BENCH_tune.json``.

Tunes paper-default DCQCN on one CLOS incast three ways and records
the results:

  * ``grad`` — :class:`repro.tune.GradTuner` (jax.grad through the
    temperature-smoothed dt-scan), the PR's headline path.  Its
    hard-model improvement over the paper defaults is the regression
    gate.
  * ``es`` — a short antithetic-ES run on the exact hard model (the
    no-smoothing cross-check; its populations ride ``Sweep.run``).
  * ``pareto`` — a goodput vs p99-slowdown scalarisation sweep
    (``pareto_autotune``); the non-dominated set is the record's
    trade-off curve entry.

Every invocation appends a run record to ``BENCH_tune.json`` at the
repo root.  ``--quick`` shrinks iteration counts to CI size (the
committed baseline is a quick record, so the CI gate compares
like-for-like).

Regression gate (the CI ``tune-smoke`` job): ``check_regression``
fails when the gradient tuner no longer beats the paper defaults on
the *hard* model, when its improvement margin drops below
``(1 - TOLERANCE) x`` the committed baseline's margin (the demand is
capped at ``MIN_MARGIN`` so cross-runner optimisation variance cannot
flake the gate — a broken tuner lands at ~0, a working one at ~0.1),
or when the Pareto front comes back empty.
"""

from __future__ import annotations

import json
import os
import time

BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_tune.json")

#: fail check_regression when the grad tuner's hard-model improvement
#: falls below (1 - TOLERANCE) x the committed baseline's margin
TOLERANCE = 0.20

#: ... but never demand more than this absolute margin — the gate must
#: catch "tuner broken" (margin ~0), not flake on cross-runner
#: optimisation variance (objective is a weighted scalarisation of
#: O(1) terms; 0.01 is far above f32 noise and far below the ~0.1 a
#: working tuner finds on this incast)
MIN_MARGIN = 0.01

N_STEPS = 3000
SCENARIO = "incast8"

GRAD_KW = dict(iters=12, lr=0.25, temperature=0.2)
GRAD_KW_QUICK = dict(iters=8, lr=0.25, temperature=0.2)
ES_KW = dict(iters=4, pop=8, sigma=0.3, lr=0.4)
ES_KW_QUICK = dict(iters=2, pop=4, sigma=0.3, lr=0.4)
PARETO_WEIGHTS = 3
PARETO_WEIGHTS_QUICK = 2


def _problem():
    from repro.core import CCScheme, PAPER_CONFIG, ScenarioSpec
    cfg = PAPER_CONFIG.replace(scheme=CCScheme.DCQCN)
    return cfg, ScenarioSpec.incast(8)


def run_tune(quick: bool = False) -> dict:
    """The tuning runs: returns the BENCH_tune run record."""
    from repro.tune import autotune, pareto_autotune

    cfg, scn = _problem()
    grad_kw = GRAD_KW_QUICK if quick else GRAD_KW
    es_kw = ES_KW_QUICK if quick else ES_KW
    n_weights = PARETO_WEIGHTS_QUICK if quick else PARETO_WEIGHTS

    t0 = time.perf_counter()
    grad = autotune(cfg, scn, method="grad", n_steps=N_STEPS,
                    seed=0, **grad_kw)
    es = autotune(cfg, scn, method="es", n_steps=N_STEPS,
                  seed=0, **es_kw)
    pareto = pareto_autotune(cfg, scn, axes=("goodput", "p99_slowdown"),
                             n_weights=n_weights, method="grad",
                             n_steps=N_STEPS, seed=0,
                             **dict(grad_kw, iters=max(
                                 grad_kw["iters"] // 2, 4)))
    wall = time.perf_counter() - t0

    front = [{k: f[k] for k in ("weights", "params", "axis_values")}
             for f in pareto["front"]]
    print(f"tune: grad {grad.baseline_value:+.4f} -> "
          f"{grad.best_value:+.4f} (margin {grad.improvement:+.4f}), "
          f"es margin {es.improvement:+.4f}, "
          f"pareto front {len(front)} point(s), {wall:.1f}s")
    try:
        from ._env import bench_env
    except ImportError:              # `python benchmarks/tune_bench.py`
        from _env import bench_env
    return {
        "unix_time": int(time.time()),
        **bench_env(),
        "quick": quick,
        "scenario": SCENARIO,
        "n_steps": N_STEPS,
        "wall_s": round(wall, 2),
        "grad": grad.to_record(),
        "es": es.to_record(),
        "pareto": {"axes": pareto["axes"], "front": front},
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
    print(f"appended tune record -> {path} ({len(doc['runs'])} runs)")


def check_regression(record: dict, baseline: dict | None = None,
                     tolerance: float = TOLERANCE) -> list[str]:
    """Failures when ``record`` breaks the autotuning contracts.

    ``baseline`` defaults to the *first* run in the committed
    BENCH_tune.json (the frozen reference).
    """
    fails = []
    g = record["grad"]
    if not g["improved"]:
        fails.append(
            f"grad tuner no longer beats paper-default DCQCN on the "
            f"hard model (baseline {g['baseline_value']:+.4f}, best "
            f"{g['best_value']:+.4f})")
    if not record["pareto"]["front"]:
        fails.append("pareto_autotune returned an empty front")

    if baseline is None:
        runs = load_bench().get("runs", [])
        baseline = runs[0] if runs else None
    if baseline is None:
        fails.append("no committed BENCH_tune.json baseline")
        return fails
    floor = min((1.0 - tolerance) * baseline["grad"]["improvement"],
                MIN_MARGIN)
    floor = max(floor, 0.0)
    if g["improvement"] < floor:
        fails.append(
            f"grad improvement {g['improvement']:+.4f} < {floor:+.4f} "
            f"(baseline margin {baseline['grad']['improvement']:+.4f} "
            f"- {tolerance:.0%}, demand capped at {MIN_MARGIN})")
    return fails


def main(quick: bool = False, check: bool = False) -> list[tuple]:
    """run.py section hook: tune, append, optionally gate."""
    record = run_tune(quick=quick)
    fails = check_regression(record) if check else []
    append_bench_record(record)
    rows = [
        ("tune.grad_margin", 0.0,
         f"{record['grad']['improvement']:+.4f}"),
        ("tune.grad_goodput", 0.0,
         f"{record['grad']['baseline_metrics']['goodput']:.3f}->"
         f"{record['grad']['best_metrics']['goodput']:.3f}"),
        ("tune.grad_p99", 0.0,
         f"{record['grad']['baseline_metrics']['p99_slowdown']:.1f}->"
         f"{record['grad']['best_metrics']['p99_slowdown']:.1f}"),
        ("tune.es_margin", 0.0,
         f"{record['es']['improvement']:+.4f}"),
        ("tune.front_size", 0.0,
         str(len(record["pareto"]["front"]))),
    ]
    for f in fails:
        rows.append(("tune.REGRESSION", 0.0, f))
    return rows


if __name__ == "__main__":
    import sys
    rows = main(quick="--quick" in sys.argv, check="--check" in sys.argv)
    for row in rows:
        print(",".join(str(x) for x in row))
    if any("REGRESSION" in r[0] for r in rows):
        raise SystemExit(1)
