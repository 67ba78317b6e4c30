"""Closed-loop sweep launches of a multi-path cell: the mix's grid crossed
with its routing modes (``grid.routing``: min, valiant, ugal), each
point's scenario built with the configuration's ``routing`` (``n_paths``
candidate paths a flow, ``vc_mode``), launched back to back through
``Sweep.run``, each launch from t=0, as ``sweep_closed`` does.

The seed draws the Valiant route seed (``ScenarioSpec.route_seed``)
besides what the mix's scenes draw; neither changes a compiled shape.
Set-up builds the sweep under a budget of ``BUILD_BUDGET_S`` seconds,
past which the run stops with an error and prints no result, and runs
one whole launch.  After the window every run of the last launch is
compared with the configuration's reference (``bench/reference_
adaptive.py``): ``harness.compare``'s numbers, plus ``nonmin_gap`` and
``path_flips`` (``path_gaps`` of the reference) and ``flows_apart``
(the share of flows that depart from the reference), and every other launch
must equal the last bit for bit (``launch_mismatch``).  A traced run
also puts the traced device time down to the program's phase scopes
(``bench/scopes.py``) and reports the path selection's share.
"""

import contextlib
import dataclasses
import importlib
import json
import signal
import time

import numpy as np

from bench import harness, traffic
from bench.bytes_model import fluid_step_bytes
from bench.lookup import module

#: seconds set-up may spend building the sweep (scenarios and routes)
BUILD_BUDGET_S = 120


@dataclasses.dataclass
class Window:
    """What ``closed_loop`` measured."""

    results: list             # every launch's SweepResult
    n_steps: int
    trace_every: int
    seconds: float            # first call to last return
    notes: list
    ctx: dict                 # what the per-layer readers read


def _over_budget(signum, frame):
    raise TimeoutError(f"set-up budget: building the sweep (bench.build) took over "
                       f"{BUILD_BUDGET_S} s; the program cannot build this cell's "
                       f"routes in time")


#: JAX's compile events: tracing, lowering, and compile or persistent-cache load
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


@contextlib.contextmanager
def compile_events():
    """Collects the seconds of every ``COMPILE_EVENTS`` event in the block."""
    import jax.monitoring as mon

    seconds = []

    def on_duration(event, duration, **kw):
        if event in COMPILE_EVENTS:
            seconds.append(duration)

    mon.register_event_duration_secs_listener(on_duration)
    try:
        yield seconds
    finally:
        mon.unregister_event_duration_listener(on_duration)


def closed_loop(run, mix: dict, build, launch_kw: dict = None) -> Window:
    """Build the sweep (``build()``, under the budget), warm it up with
    one whole launch, then launch until ``--seconds`` have passed; the
    profiler covers the first ``traced_launches`` launches (all where the
    mix sets none)."""
    from repro.core import SWEEP_EXEC_CACHE

    spans, kw = run.spans, dict(launch_kw or {})
    n_steps, k = int(mix["n_steps"]), int(mix["trace_every"])
    old = signal.signal(signal.SIGALRM, _over_budget)
    signal.alarm(BUILD_BUDGET_S)
    try:
        with spans("bench.build"):
            sweep = build()
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
    host_build_s = spans.total("bench.build")
    c0 = SWEEP_EXEC_CACHE.stats()
    with spans("bench.warmup"), compile_events() as lazy:
        sweep.run(n_steps=n_steps, trace_every=k, **kw)
    # a mesh launch caches a jitted callable, built in no time, that
    # compiles at its first call: there JAX's compile events of the
    # warm-up count (the sweep's and the staging ops')
    compile_s = (sum(lazy) if kw.get("mesh") is not None
                 else (SWEEP_EXEC_CACHE.stats() - c0).build_s)

    c1, k1 = SWEEP_EXEC_CACHE.stats(), run.compiles.snapshot()
    results = []

    def launch():
        with spans("bench.launch"):
            results.append(sweep.run(n_steps=n_steps, trace_every=k, **kw))

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
    notes = [f"setup_phases host_build_s={host_build_s} compile_s={compile_s} "
             f"warmup_s={spans.total('bench.warmup')}",
             f"launches={len(results)} runs={len(sweep.points)} steps={n_steps} "
             f"window_s={t_last - t_first}",
             f"exec_cache_misses_in_window={misses} compiles_in_window={compiles}"]
    ctx = dict(host_build_s=host_build_s, compile_s=compile_s,
               steps_simulated=(n_traced or len(results)) * n_steps)
    return Window(results=results, n_steps=n_steps, trace_every=k,
                  seconds=t_last - t_first, notes=notes, ctx=ctx)


def route_seed(seed: int) -> int:
    """The Valiant route seed a run's ``--seed`` draws."""
    return int(traffic.rng_for(seed, "route_seed").integers(2 ** 31))


def points(mix: dict, config: dict, seed: int) -> list:
    """[(name, scheme, routing mode, {dotted param: value}, Flows)]."""
    return [(f"{name}/{mode}", scheme, mode, over, flows)
            for name, scheme, over, flows in traffic.grid_points(mix, config, seed)
            for mode in mix["grid"]["routing"]]


def program_point(config: dict, point: tuple, seed: int) -> tuple:
    """One point as the program's (name, CCSpec, ScenarioSpec)."""
    name, scheme, mode, over, flows = point
    routing = config["routing"]
    spec = dataclasses.replace(harness.cc_spec(config, scheme, over), routing=mode)
    scn = dataclasses.replace(harness.scenario_spec(config, flows),
                              n_paths=int(routing["n_paths"]), route_seed=route_seed(seed),
                              vc_mode=routing["vc_mode"])
    return name, spec, scn


def reference(config: dict):
    """The configuration's reference module (its ``reference`` file)."""
    return importlib.import_module(config["reference"][:-len(".py")].replace("/", "."))


def ref_point(config: dict, point: tuple, seed: int):
    """One point as the reference's ``Run``."""
    _, scheme, mode, over, flows = point
    base = harness.ref_run(config, scheme, over, flows)
    fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
    return reference(config).Run(**fields, routing=mode,
                                 n_paths=int(config["routing"]["n_paths"]),
                                 route_seed=route_seed(seed))


def program_view(sim) -> dict:
    """``harness.program_view`` plus the traced count of flows on a
    detour and each flow's final candidate."""
    view = harness.program_view(sim)
    view["trace"]["n_nonmin"] = np.asarray(sim.n_nonmin)
    view["final"]["path_idx"] = np.asarray(sim.final.path_idx)
    return view


#: a flow is apart when it departs by more than this share (see ``flows_apart``)
APART = 0.01


def flows_apart(prog: list, good: list, config: dict) -> float:
    """Share of a run's flows, the widest over the runs, whose delivered
    bytes at some trace sample depart from the reference's by more than
    ``APART`` of the run's largest final delivered volume, or whose final
    rate departs by more than ``APART`` of line rate.  One pause, mark or
    path decided the other way moves a few flows; a fault in how bytes or
    rates are kept per flow moves most."""
    line = float(config["link"]["line_rate"])
    worst = 0.0
    for p, r in zip(prog, good):
        want = np.asarray(r["trace"]["delivered"], np.float64)
        F = want.shape[1]
        got = np.asarray(p["trace"]["delivered"], np.float64)[:, :F]
        apart = (np.abs(got - want) > APART * max(want[-1].max(), 1.0)).any(axis=0)
        rate = np.asarray(p["final"]["rate"], np.float64)[:F]
        apart |= np.abs(rate - np.asarray(r["final"]["rate"], np.float64)) > APART * line
        worst = max(worst, float(apart.mean()))
    return worst


def gaps(prog: list, good: list, config: dict) -> dict:
    """Every number the cell compares: ``harness.compare``'s, the
    reference's ``path_gaps`` and ``flows_apart``."""
    return dict(harness.compare(prog, good, config), **reference(config).path_gaps(prog, good),
                flows_apart=flows_apart(prog, good, config))


def check(prog: list, refs: list, config: dict, n_steps: int, trace_every: int,
          dtype: str = "float32") -> dict:
    """The gaps of the program's runs ``prog`` (``program_view``s) to the
    reference computed in ``dtype`` for ``refs``."""
    return gaps(prog, reference(config).simulate(refs, n_steps, trace_every, dtype), config)


def select_us(run, steps: int, notes: list):
    """Device microseconds a step under the ``fluid.select`` scope, from
    the traced run's trace (left in place for ``bench/run.py``)."""
    from bench import scopes, trace
    try:
        from repro.core.obs import sweep_op_scopes
    except ImportError:           # a program without scopes
        return None
    red = scopes.reduce_scoped(scopes.load_events(trace.find_xplane(run.trace_dir)),
                               sweep_op_scopes(), n_chips=len(run.devs))
    notes.append(f"scope_s {json.dumps(red['scope_s'])}")
    sel = red["scope_s"].get("fluid.select")
    return sel / steps * 1e6 if sel else None


def run(cell: dict, config: dict, mix: dict, run) -> harness.Outcome:
    from repro.core import Sweep

    pts = points(mix, config, run.seed)
    win = closed_loop(run, mix, lambda: Sweep(
        [program_point(config, p, run.seed) for p in pts]))

    flows_real = sum(len(p[-1]) for p in pts)
    rate = len(win.results) * win.n_steps * flows_real / win.seconds
    views = [[program_view(res[i]) for i in range(len(pts))] for res in win.results]
    digests = [harness.digest(v) for v in views]
    mismatch = sum(d != digests[-1] for d in digests)
    t_ref = time.perf_counter()
    gaps = check(views[-1], [ref_point(config, p, run.seed) for p in pts], config,
                 win.n_steps, win.trace_every)
    ref_s = time.perf_counter() - t_ref
    nonmin = {p[0]: [int(v["trace"]["n_nonmin"].min()), int(v["trace"]["n_nonmin"].max())]
              for p, v in zip(pts, views[-1])}

    fab = module("fabrics", config["fabric"]["kind"])
    L, H = fab.links(config["fabric"])
    K, V = int(config["routing"]["n_paths"]), int(config["link"]["n_vcs"])
    H = fab.DETOUR_HOPS if K > 1 else H
    ctx = dict(win.ctx, step_bytes=sum(fluid_step_bytes(len(p[-1]), K, H, L, V) for p in pts))
    notes = win.notes + [f"flows_real={flows_real} route_seed={route_seed(run.seed)}",
                         f"n_nonmin_min_max {json.dumps(nonmin)}",
                         f"reference_s={ref_s} gaps={json.dumps(gaps)}"]
    if run.trace:
        ctx["select_us"] = select_us(run, ctx["steps_simulated"], notes)
    values = dict(gaps, launch_mismatch=mismatch)
    compared = {name: (values[name], lim) for name, lim in run.limits.items()}
    return harness.Outcome(e2e=dict(flow_steps_per_s=rate), ctx=ctx, compared=compared,
                           attempted=len(win.results), failed=0, notes=notes)
