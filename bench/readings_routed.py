"""Readings behind the limits of the multi-path cells (``bench/drivers/
sweep_routed.py``), as ``bench/readings.py`` takes them for the others.

    python3 bench/readings_routed.py control --workload <cell> --seeds <a,b,...>
    python3 bench/readings_routed.py witness --workload <cell> --seeds <a,b,...>

``control`` puts the configuration's reference computed in bfloat16 in
the program's place, at the cell's own size, and prints its gaps to the
float32 reference (the upper readings); ``witness`` does so for the
reference in float64 and in float32 with its per-link sums reversed.
The program's readings (the lower ones) are the compared numbers of
``bench/run.py``'s runs.  Both run on the host alone.  One JSON object a
line on standard output.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv=None):
    from bench import harness, traffic
    from bench.lookup import module

    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("control", "witness"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = {w["name"]: w for w in json.load(f)["workloads"]}[args.workload]
    config, mix = harness.load_config(cell["config"]), traffic.load(cell["traffic"])
    drv = module("drivers", mix["driver"])
    ref = drv.reference(config)
    others = {"control": [("bfloat16", dict(dtype="bfloat16"))],
              "witness": [("float64", dict(dtype="float64")),
                          ("float32_reversed", dict(dtype="float32", sum_order="reverse"))]}
    n, k = int(mix["n_steps"]), int(mix["trace_every"])
    for seed in (int(s) for s in args.seeds.split(",")):
        pts = [drv.ref_point(config, p, seed) for p in drv.points(mix, config, seed)]
        good = ref.simulate(pts, n, k, "float32")
        for name, kw in others[args.what]:
            t = time.perf_counter()
            low = ref.simulate(pts, n, k, **kw)
            gaps = drv.gaps(low, good, config)
            print(json.dumps({"what": args.what, "as": name, "cell": cell["name"],
                              "seed": seed, "runs": len(pts),
                              "seconds": time.perf_counter() - t, **gaps}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
