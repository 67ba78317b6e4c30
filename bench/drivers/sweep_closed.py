"""Closed-loop sweep launches: one ``Sweep`` of the mix's grid, launched
back to back through ``Sweep.run``, each launch from t=0.

Set-up builds the sweep (``bench.build``) and runs one whole launch
(``bench.warmup``), which compiles or loads every program the window
uses.  The window launches until ``--seconds`` have passed; the rate is
the real flow-steps of every launch over the time from the first call to
the last return.  After it, every run of the last launch is compared with
the reference (``harness.compare``), and every other launch must equal the
last bit for bit (``launch_mismatch``); the cell's limits file
(``bench/limits/<cell>.json``) names the numbers held and their limits.
"""

import json
import time

from bench import harness, traffic
from bench.bytes_model import fluid_step_bytes
from bench.lookup import module

def run(cell: dict, config: dict, mix: dict, run) -> harness.Outcome:
    from repro.core import SWEEP_EXEC_CACHE, Sweep

    spans, seed = run.spans, run.seed
    n_steps, k = int(mix["n_steps"]), int(mix["trace_every"])
    pts = traffic.grid_points(mix, config, seed)
    with spans("bench.build"):
        sweep = Sweep([(name, harness.cc_spec(config, scheme, over),
                        harness.scenario_spec(config, flows))
                       for name, scheme, over, flows in pts])
    host_build_s = spans.total("bench.build")
    c0 = SWEEP_EXEC_CACHE.stats()
    with spans("bench.warmup"):
        sweep.run(n_steps=n_steps, trace_every=k)
    compile_s = (SWEEP_EXEC_CACHE.stats() - c0).build_s

    c1, k1 = SWEEP_EXEC_CACHE.stats(), run.compiles.snapshot()
    results = []

    def launch():
        with spans("bench.launch"):
            results.append(sweep.run(n_steps=n_steps, trace_every=k))

    # the profiler covers the first ``traced_launches`` launches (every
    # launch of the window where the mix sets none)
    n_traced = int(mix.get("traced_launches", 0))
    run.window_begin()
    with spans("bench.window"):
        t_first = time.perf_counter()
        if run.trace:
            run.trace_start()
            with spans("bench.traced"):
                while (len(results) < n_traced if n_traced else
                       not results or time.perf_counter() - t_first < run.seconds):
                    launch()
            run.trace_stop()
        while not results or time.perf_counter() - t_first < run.seconds:
            launch()
        t_last = time.perf_counter()
    misses = (SWEEP_EXEC_CACHE.stats() - c1).misses
    compiles = {n: v - k1[n] for n, v in run.compiles.snapshot().items()}
    run.read_memory()

    flows_real = sum(len(f) for _, _, _, f in pts)
    flow_steps = len(results) * n_steps * flows_real
    rate = flow_steps / (t_last - t_first)

    views = [[harness.program_view(res[i]) for i in range(len(pts))] for res in results]
    digests = [harness.digest(v) for v in views]
    mismatch = sum(d != digests[-1] for d in digests)
    refs = [harness.ref_run(config, scheme, over, flows) for _, scheme, over, flows in pts]
    t_ref = time.perf_counter()
    gaps = harness.reference_check(views[-1], refs, config, n_steps, k)
    ref_s = time.perf_counter() - t_ref

    L, H = module("fabrics", config["fabric"]["kind"]).links(config["fabric"])
    step_bytes = sum(fluid_step_bytes(len(f), 1, H, L) for _, _, _, f in pts)
    ctx = dict(host_build_s=host_build_s, compile_s=compile_s,
               steps_simulated=(n_traced or len(results)) * n_steps,
               step_bytes=step_bytes)
    notes = [f"setup_phases host_build_s={host_build_s} compile_s={compile_s} "
             f"warmup_s={spans.total('bench.warmup')}",
             f"launches={len(results)} runs={len(pts)} flows_real={flows_real} "
             f"steps={n_steps} window_s={t_last - t_first}",
             f"exec_cache_misses_in_window={misses} compiles_in_window={compiles}",
             f"reference_s={ref_s} gaps={json.dumps(gaps)}"]
    # the cell's limits file names the numbers it holds
    values = dict(gaps, launch_mismatch=mismatch)
    compared = {name: (values[name], lim) for name, lim in run.limits.items()}
    return harness.Outcome(e2e=dict(flow_steps_per_s=rate), ctx=ctx, compared=compared,
                           attempted=len(results), failed=0, notes=notes)
