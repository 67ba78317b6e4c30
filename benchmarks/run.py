"""Benchmark entrypoint — one section per paper table/figure + the
beyond-paper harnesses.  Prints ``name,us_per_call,derived`` CSV.

  fig2.*       paper Fig. 2 (aggregate throughput, completion times)
  fig3.*       paper Fig. 3 (per-flow bandwidth)
  cc_scale.*   DC-scale reaction-point + fluid stepping throughput
  net_scale.*  repro.net fabric-family scaling matrix (also ``--scale``)
  roofline.*   §Roofline terms per (arch x shape) from dry-run artifacts
  cosim.*      collective traffic x CC scheme co-simulation
  train.*      tiny end-to-end training-step wall time (CPU)

``--smoke`` runs one tiny end-to-end Sweep (scheme x scenario grid,
single jitted launch) and exits non-zero on failure — the CI hook.
``--scale`` runs only the fabric matrix and appends a record to
``BENCH_net.json`` (``--quick`` shrinks it to CI size).
``--perf`` runs the fluid hot-loop F/L scaling curve (legacy scatter
path vs fused one-pass reduction vs the whole-step megakernel) and
appends a record to ``BENCH_fluid.json``; with ``--check`` it exits
non-zero when the fused/scat speedup falls below 80% of the committed
baseline's (floor capped at 2.0x for cross-runner noise) or when the
megakernel's per-substep op reduction drops below 5x / regresses >20%
vs baseline (the launch-fusion gate; CPU wall clock runs the
interpreter, so the jaxpr op count is the machine-stable metric) —
the CI perf-smoke gate.
``--serve`` replays the mixed what-if query stream through
``CCQueryEngine`` and appends a record to ``BENCH_serve.json``; with
``--check`` it exits non-zero on a p99 latency regression vs the
committed baseline, a compiled-executable hit-rate collapse, or a
token bucket that fails to throttle an over-rate burst (the CI
serve-smoke gate).
``--tune`` runs the autotuning harness (GradTuner + ESTuner + a
Pareto scalarisation sweep on paper-default DCQCN, one CLOS incast)
and appends a record to ``BENCH_tune.json``; with ``--check`` it exits
non-zero when the tuned config no longer beats the paper defaults on
the hard model, the improvement margin regresses past the committed
baseline's, or the Pareto front is empty (the CI tune-smoke gate).
``--fleet`` runs the same ragged grid as a single ``Sweep.run()``
launch and as a threaded work-stealing fleet (streaming + journal) and
appends a record to ``BENCH_fleet.json``; with ``--check`` it exits
non-zero when the merged fleet result is not bitwise the single
launch, the envelope plan compiled more than once, any shard was
Abandoned, or the scheduling overhead regresses past the committed
baseline (the CI fleet-smoke gate).
``--cc-matrix`` enumerates the ``repro.core.cc`` stage registries
(every marking x notification x reaction combination) as ONE Sweep
launch, appends the rows to ``BENCH_fluid.json`` under ``cc_matrix``
and exits non-zero if the matrix needed more than one compile — then
repeats the matrix through the megakernel (``use_kernels="mega"``),
where the same one-build assertion must hold on the single
pallas_call.
"""

from __future__ import annotations

import argparse
import time
import traceback


def _section(name: str, fn):
    """Run one section; a failure becomes a ``<name>.ERROR`` row (with
    its traceback on stderr) so later sections still run, and the
    caller exits non-zero on it."""
    t0 = time.perf_counter()
    try:
        rows = fn()
    except Exception as e:   # noqa: BLE001 — reported, then exit != 0
        traceback.print_exc()
        rows = [(f"{name}.ERROR", 0.0, repr(e)[:120])]
    dt = time.perf_counter() - t0
    rows.append((f"{name}.section_wall_s", dt * 1e6, f"{dt:.1f}s"))
    return rows


def bench_train_step() -> list[tuple]:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models import transformer
    from repro.models.layers import init_params
    from repro.train.step import (StepConfig, init_train_state,
                                  make_train_step)
    from repro.data import DataConfig, SyntheticLM

    out = []
    for arch in ("qwen2.5-32b", "mixtral-8x22b", "falcon-mamba-7b"):
        cfg = get_smoke_config(arch)
        params = init_params(transformer.param_defs(cfg), 0, jnp.float32)
        sc = StepConfig()
        state = init_train_state(cfg, params, sc)
        step = jax.jit(make_train_step(cfg, sc))
        ds = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64,
                                    global_batch=4))
        b = ds.batch_at(0)
        state, m = step(state, b)          # compile
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for i in range(5):
            state, m = step(state, ds.batch_at(i + 1))
        jax.block_until_ready(m["loss"])
        us = (time.perf_counter() - t0) / 5 * 1e6
        out.append((f"train.smoke.{arch}", us,
                    f"loss={float(m['loss']):.3f}"))
    return out


def smoke() -> int:
    """Tiny sweep, end to end: scheme x scenario grid in one launch.

    Checks the load-bearing invariants cheaply (sub-minute on CPU):
    the sweep runs as one jitted call, per-point views slice cleanly,
    and DCQCN-Rev's fair-share behaviour shows up on the small incast.
    """
    from repro.core import CCScheme, PAPER_CONFIG, ScenarioSpec, Sweep

    cfg = PAPER_CONFIG
    t0 = time.perf_counter()
    sweep = Sweep.grid(
        configs={s.name: cfg.replace(scheme=s)
                 for s in (CCScheme.DCQCN, CCScheme.DCQCN_REV)},
        scenarios={"hol": ScenarioSpec.paper_incast(roll=0),
                   "incast2": ScenarioSpec.incast(2, victim=False)})
    res = sweep.run(n_steps=4000)
    wall = time.perf_counter() - t0
    summary = res.summary()
    for name, row in summary.items():
        print(f"smoke.{name}: agg={row['aggregate_gbps']:.2f}GB/s "
              f"peak_q={row['peak_queue_kb']:.0f}KB")
    rev = res["DCQCN_REV/hol"].mean_throughput_while_active()
    dcq = res["DCQCN/hol"].mean_throughput_while_active()
    ok = (len(summary) == 4
          and rev[4] > dcq[4]              # Rev protects the victim
          and rev.sum() > dcq.sum())       # ... and total throughput
    print(f"smoke: 4-point sweep in {wall:.1f}s -> "
          f"{'OK' if ok else 'FAILED'}")
    return 0 if ok else 1


def _print_rows(all_rows) -> None:
    print("name,us_per_call,derived")
    for name, us, derived in all_rows:
        print(f"{name},{us:.2f},{derived}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one tiny end-to-end sweep (CI tier-1 hook)")
    ap.add_argument("--scale", action="store_true",
                    help="fabric-family scaling matrix -> BENCH_net.json")
    ap.add_argument("--perf", action="store_true",
                    help="fluid hot-loop scaling curve -> BENCH_fluid.json")
    ap.add_argument("--check", action="store_true",
                    help="with --perf: fail when fused/scat speedup "
                         "drops below 80%% of the committed "
                         "BENCH_fluid.json baseline (floor capped at "
                         "2.0x for cross-runner noise) or the "
                         "megakernel op reduction below 5x/-20%%")
    ap.add_argument("--serve", action="store_true",
                    help="what-if query engine replay -> BENCH_serve.json "
                         "(--check gates on p99 regression, hit-rate "
                         "collapse and throttling)")
    ap.add_argument("--tune", action="store_true",
                    help="CC autotuning harness -> BENCH_tune.json "
                         "(--check gates on the tuned-beats-default "
                         "margin and a non-empty Pareto front)")
    ap.add_argument("--fleet", action="store_true",
                    help="work-stealing fleet vs single-launch sweep "
                         "-> BENCH_fleet.json (--check gates on "
                         "bitwise fidelity, one compile per signature, "
                         "zero Abandoned shards and the scheduling-"
                         "overhead regression)")
    ap.add_argument("--cc-matrix", action="store_true", dest="cc_matrix",
                    help="stage-registry combination sweep (marking x "
                         "notification x reaction, one jit) -> "
                         "BENCH_fluid.json")
    ap.add_argument("--quick", action="store_true",
                    help="with --scale/--perf/--cc-matrix/--serve/"
                         "--tune/--fleet: CI-sized run")
    args = ap.parse_args()
    if __package__:
        from ._env import use_compile_cache
    else:                    # `python benchmarks/run.py` (no package ctx)
        from _env import use_compile_cache
    use_compile_cache()
    if args.smoke:
        raise SystemExit(smoke())

    if __package__:
        from . import (ablation, cc_matrix, cc_scale, cosim,
                       fig2_throughput, fig3_perflow, fleet_bench,
                       net_scale, perf_fluid, roofline, serve_bench,
                       tune_bench)
    else:                    # `python benchmarks/run.py` (no package ctx)
        import ablation, cc_matrix, cc_scale, cosim        # noqa: E401
        import fig2_throughput, fig3_perflow, fleet_bench  # noqa: E401
        import net_scale, perf_fluid, roofline             # noqa: E401
        import serve_bench, tune_bench                     # noqa: E401

    if args.tune:
        rows = _section("tune",
                        lambda: tune_bench.main(quick=args.quick,
                                                check=args.check))
        _print_rows(rows)
        if any(".ERROR" in r[0] or "REGRESSION" in r[0] for r in rows):
            raise SystemExit(1)
        return

    if args.serve:
        rows = _section("serve",
                        lambda: serve_bench.main(quick=args.quick,
                                                 check=args.check))
        _print_rows(rows)
        if any(".ERROR" in r[0] or "REGRESSION" in r[0] for r in rows):
            raise SystemExit(1)
        return

    if args.fleet:
        rows = _section("fleet",
                        lambda: fleet_bench.main(quick=args.quick,
                                                 check=args.check))
        _print_rows(rows)
        if any(".ERROR" in r[0] or "REGRESSION" in r[0] for r in rows):
            raise SystemExit(1)
        return

    if args.cc_matrix:
        rows = _section("cc_matrix",
                        lambda: cc_matrix.main(quick=args.quick))
        _print_rows(rows)
        if any(".ERROR" in r[0] or "RECOMPILE" in r[0] for r in rows):
            raise SystemExit(1)
        return

    if args.scale:
        rows = _section("net_scale",
                        lambda: net_scale.main(quick=args.quick))
        _print_rows(rows)
        if any(".ERROR" in r[0] for r in rows):
            raise SystemExit(1)
        return

    if args.perf:
        rows = _section("perf_fluid",
                        lambda: perf_fluid.main(quick=args.quick,
                                                check=args.check))
        _print_rows(rows)
        if any(".ERROR" in r[0] or "REGRESSION" in r[0] for r in rows):
            raise SystemExit(1)
        return

    all_rows = []
    all_rows += _section("fig2", fig2_throughput.main)
    all_rows += _section("fig3", fig3_perflow.main)
    all_rows += _section("ablation", ablation.main)
    all_rows += _section("cc_matrix", lambda: cc_matrix.main(quick=True))
    all_rows += _section("cc_scale", cc_scale.main)
    all_rows += _section("net_scale", net_scale.main)
    all_rows += _section("perf_fluid", lambda: perf_fluid.main(quick=True))
    all_rows += _section("roofline", roofline.main)
    all_rows += _section("cosim", cosim.main)
    all_rows += _section("train", bench_train_step)
    _print_rows(all_rows)
    if any(".ERROR" in r[0] for r in all_rows):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
