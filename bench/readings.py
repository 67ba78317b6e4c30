"""Readings behind the benchmark's limits, run on the chip.

    python3 bench/readings.py program --workload <cell> --seeds <a,b,...> [--seconds s]
    python3 bench/readings.py control --workload <cell> --seeds <a,b,...>
    python3 bench/readings.py witness --workload <cell> --seeds <a,b,...>
    python3 bench/readings.py trace --workload <cell> --out <file> [--seconds s]

``program`` runs the cell as ``bench/run.py`` does, once per seed in one
process, and prints each run's gaps to the reference (the lower readings
of the limits).  ``control`` puts the reference computed in bfloat16 in
the program's place, at the cell's own size, and prints its gaps to the
float32 reference (the upper readings).  ``witness`` prints the gaps to
the float32 reference of two other sound implementations: the reference
in float64, and in float32 with its per-link sums in reverse order (how
far rounding alone moves the model).  ``trace`` records a small trace of
a tiny sweep into ``--out`` (the reduction test's data).  One JSON object
per line on standard output.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def _cell(name):
    from bench import harness, traffic
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[name]
    return bench, cell, harness.load_config(cell["config"]), traffic.load(cell["traffic"])


def control_points(config, mix, seed):
    """The reference runs a cell checks: every grid point of the sweep."""
    from bench import harness, traffic
    return [harness.ref_run(config, sch, over, fl)
            for _, sch, over, fl in traffic.grid_points(mix, config, seed)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("program", "control", "witness", "trace"))
    ap.add_argument("--out", default="")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    bench, cell, config, mix = _cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]

    if args.what in ("control", "witness"):
        from bench import harness, reference
        others = {"control": [("bfloat16", dict(dtype="bfloat16"))],
                  "witness": [("float64", dict(dtype="float64")),
                              ("float32_reversed", dict(dtype="float32",
                                                        sum_order="reverse"))]}
        for seed in seeds:
            pts = control_points(config, mix, seed)
            n, k = int(mix["n_steps"]), int(mix["trace_every"])
            good = reference.simulate(pts, n, k, "float32")
            for name, kw in others[args.what]:
                t = time.perf_counter()
                gaps = harness.compare(reference.simulate(pts, n, k, **kw), good, config)
                print(json.dumps({"what": args.what, "as": name, "cell": cell["name"],
                                  "seed": seed, "runs": len(pts),
                                  "seconds": time.perf_counter() - t, **gaps}), flush=True)
        return 0

    from bench import env
    from bench.run import run_cell
    devs = env.devices(int(cell["chips"]))
    peaks = env.peaks(devs[0].device_kind)
    env.use_compile_cache()
    if args.what == "trace":
        from bench import harness, traffic
        data = os.path.join(ROOT, "bench", "tests", "data")
        mix = traffic.load("paper_grid_tiny", data)
        mix = dict(mix, n_steps=40, scenes=mix["scenes"][:1],
                   grid=dict(mix["grid"], schemes=mix["grid"]["schemes"][:2],
                             params={"dcqcn.kmin": [15360.0]}))
        line, notes = run_cell(bench, cell, harness.load_config("clos64"), mix, args.seed,
                               args.seconds, True, devs, peaks, keep_trace_to=args.out)
        print(json.dumps({"what": "trace", "line": line, "notes": notes}), flush=True)
        return 0
    for seed in seeds:
        t = time.perf_counter()
        line, notes = run_cell(bench, cell, config, mix, seed, args.seconds,
                               False, devs, peaks)
        print(json.dumps({"what": "program", "cell": cell["name"], "seed": seed,
                          "seconds": time.perf_counter() - t, "line": line,
                          "notes": notes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
