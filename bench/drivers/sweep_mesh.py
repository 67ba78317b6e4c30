"""Closed-loop sweep launches sharded over the cell's chips: the mix's
grid as ``sweep_closed`` builds it, launched back to back through
``Sweep.run(..., mesh=sweep_mesh(chips))``, which splits the run axis
over the chips, each launch from t=0.  Set-up, window, comparison with
``bench/reference.py`` and ``launch_mismatch`` as ``sweep_closed``; the
mesh path compiles at its first call, so ``compile_s`` reads JAX's
compile events of the warm-up (``sweep_routed.closed_loop``)."""

import json
import time

from bench import harness, traffic
from bench.bytes_model import fluid_step_bytes
from bench.lookup import module


def run(cell: dict, config: dict, mix: dict, run) -> harness.Outcome:
    from repro.core import Sweep
    from repro.dist import sweep_mesh

    pts = traffic.grid_points(mix, config, run.seed)
    win = module("drivers", "sweep_routed").closed_loop(
        run, mix, lambda: Sweep([(name, harness.cc_spec(config, scheme, over),
                                  harness.scenario_spec(config, flows))
                                 for name, scheme, over, flows in pts]),
        dict(mesh=sweep_mesh(len(run.devs))))

    flows_real = sum(len(f) for _, _, _, f in pts)
    rate = len(win.results) * win.n_steps * flows_real / win.seconds
    views = [[harness.program_view(res[i]) for i in range(len(pts))] for res in win.results]
    digests = [harness.digest(v) for v in views]
    mismatch = sum(d != digests[-1] for d in digests)
    refs = [harness.ref_run(config, scheme, over, flows) for _, scheme, over, flows in pts]
    t_ref = time.perf_counter()
    gaps = harness.reference_check(views[-1], refs, config, win.n_steps, win.trace_every)
    ref_s = time.perf_counter() - t_ref

    # one chip's bytes a step: the runs split evenly over the chips, and
    # the traced busy time is the chips' mean
    L, H = module("fabrics", config["fabric"]["kind"]).links(config["fabric"])
    ctx = dict(win.ctx, step_bytes=sum(fluid_step_bytes(len(f), 1, H, L)
                                       for _, _, _, f in pts) / len(run.devs))
    notes = win.notes + [f"flows_real={flows_real} chips={len(run.devs)} "
                         f"digest={digests[-1]}",
                         f"reference_s={ref_s} gaps={json.dumps(gaps)}"]
    values = dict(gaps, launch_mismatch=mismatch)
    compared = {name: (values[name], lim) for name, lim in run.limits.items()}
    return harness.Outcome(e2e=dict(flow_steps_per_s=rate), ctx=ctx, compared=compared,
                           attempted=len(win.results), failed=0, notes=notes)
