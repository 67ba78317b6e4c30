"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the checkout root; its
configuration, traffic mix and per-layer metric readers are found by name
under ``bench/``.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``compared``: each number
checked against the reference beside its limit).  With no accelerator, or
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def per_layer_for(bench: dict, cell: str, e2e: list) -> list:
    """The per-layer metrics this cell reports."""
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in e2e]


def e2e_for(bench: dict, cell: str) -> list:
    return [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]


class Run:
    """What a driver needs of the run: seed, window, spans, trace, memory."""

    def __init__(self, seed, seconds, trace, devs, spans, compiles):
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devs, self.spans, self.compiles = devs, spans, compiles
        self.setup_s = self.setup_counts = self.driver_start_s = None
        self.memory_peak = 0
        self.limits = {}
        from bench import env
        self.trace_dir = os.path.join(env.OUT_DIR, "trace")

    def window_begin(self):
        self.setup_s = time.perf_counter() - T_START
        self.setup_counts = self.compiles.snapshot()

    def trace_start(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans stay; Python calls are not traced
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def trace_stop(self):
        import jax
        jax.profiler.stop_trace()

    def read_memory(self):
        from bench import env
        self.memory_peak = env.memory_peak_bytes(self.devs)


def run_cell(bench, cell, config, mix, seed, seconds, trace, devs, peaks,
             keep_trace_to=None):
    """Set up, measure and check one cell on ``devs``; returns the result
    line and the lines for standard error (the compared numbers last)."""
    from bench import harness
    from bench import trace as trace_mod
    from bench.lookup import module
    spans, compiles = harness.Spans(), harness.CompileCounter()
    run = Run(seed, seconds, trace, devs, spans, compiles)
    run.limits = harness.load_limits(cell["name"])
    run.driver_start_s = time.perf_counter() - T_START
    out = module("drivers", mix["driver"]).run(cell, config, mix, run)
    notes = [f"set-up compiles {run.setup_counts}",
             f"setup_s={run.setup_s} of which before the driver {run.driver_start_s}"] + out.notes

    e2e = e2e_for(bench, cell["name"])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak}
    metrics, breakdown = {}, None
    if trace:
        xplane = trace_mod.find_xplane(run.trace_dir)
        if keep_trace_to:
            shutil.copy(xplane, keep_trace_to)
        red = trace_mod.reduce(trace_mod.load_events(xplane), n_chips=len(devs))
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        notes.append(f"trace n_ops={red['n_ops']} steps_traced={out.ctx.get('steps_simulated')} "
                     f"op_counts={red['op_counts']} op_gap_s={red['op_gap_s']} "
                     f"n_op_gaps={red['n_op_gaps']}")
        breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
        ctx = dict(out.ctx, trace=red, peaks=peaks)
        for m in per_layer_for(bench, cell["name"], [x["name"] for x in e2e]):
            v = module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(out.e2e, setup_s=run.setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    compared = {k: {"value": float(v), "limit": float(lim)}
                for k, (v, lim) in out.compared.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in compared.values())
    line = {"correct": correct, "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    notes += [f"compared {k}={c['value']} limit={c['limit']}" for k, c in compared.items()]
    return line, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; cells: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]

    from bench import env, harness, traffic
    config = harness.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    import jax  # noqa: F401
    t_import = time.perf_counter() - T_START
    try:
        devs = env.devices(int(cell["chips"]))
    except env.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 3
    print(f"setup_phases import_s={t_import} "
          f"devices_s={time.perf_counter() - T_START - t_import}", file=sys.stderr)
    peaks = env.peaks(devs[0].device_kind)
    entries = env.use_compile_cache()
    print(f"compile_cache entries_at_start={entries}", file=sys.stderr)
    line, notes = run_cell(bench, cell, config, mix, args.seed, args.seconds,
                           bool(args.trace), devs, peaks)
    for note in notes:
        print(note, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
