"""The benchmark's program adapters, host spans and the `correct` comparison.

A cell names a configuration (``bench/configs/<name>.json``) and a traffic
mix (``bench/traffic/<name>.json``); the mix names its driver
(``bench/drivers/<name>.py``), which sets the program up, measures the
window and checks what the window produced against ``bench/reference.py``
with ``compare`` below.

The program is used only through its public entry points; the drivers
time it, with a ``jax.profiler.TraceAnnotation`` span around every call
into a layer (``bench.build``, ``bench.warmup``, ``bench.launch``),
``bench.window`` around the measured window and ``bench.traced`` around
the part of it the profiler records.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import time

import numpy as np

from bench import reference as ref
from bench import traffic as tr
from bench.env import BENCH
from bench.lookup import module


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_config(name: str, root: str = BENCH) -> dict:
    return load_json(root, "configs", f"{name}.json")


def load_limits(cell: str, root: str = BENCH) -> dict:
    """The cell's limit on each number compared with the reference, set
    between the largest reading of sound runs and the smallest reading of
    the control (``bench/readings.py``; the readings are in PERF.md)."""
    return load_json(root, "limits", f"{cell}.json")


# ---------------------------------------------------------------------------
# host spans and compile counters
# ---------------------------------------------------------------------------


class Spans:
    """Profiler-visible host spans, also kept as (name, t0, t1)."""

    def __init__(self):
        self.events: list = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.events.append((name, t0, time.perf_counter()))

    def total(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.events if n == name)


class CompileCounter:
    """Counts persistent-cache hits and misses and backend compiles."""

    def __init__(self):
        import jax.monitoring as mon

        self.counts = {"cache_hits": 0, "cache_misses": 0, "backend_compiles": 0}

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.counts["cache_hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.counts["cache_misses"] += 1

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.counts["backend_compiles"] += 1

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> dict:
        return dict(self.counts)


# ---------------------------------------------------------------------------
# program adapters
# ---------------------------------------------------------------------------


def params_with(config: dict, over: dict) -> dict:
    """The config's parameter groups with dotted overrides applied."""
    groups = {k: dict(config[k]) for k in ("link", "dcqcn", "rev", "sim")}
    for path, v in over.items():
        group, key = path.split(".")
        groups[group][key] = v
    return groups


def cc_spec(config: dict, scheme: str, over: dict):
    from repro.core.params import (CCSpec, DCQCNParams, LinkParams, RevParams,
                                   SimParams)
    g = params_with(config, over)
    marking, notification, reaction = config["schemes"][scheme]
    return CCSpec(marking=marking, notification=notification, reaction=reaction,
                  link=LinkParams(**g["link"]), dcqcn=DCQCNParams(**g["dcqcn"]),
                  rev=RevParams(**g["rev"]), sim=SimParams(**g["sim"]))


def scenario_spec(config: dict, flows: tr.Flows):
    from repro.core.experiments import ScenarioSpec
    fab = config["fabric"]
    fabric = module("fabrics", fab["kind"]).program(fab, flows.roll)
    return ScenarioSpec(
        kind="flowspec", fabric=fabric,
        flow_src=tuple(int(v) for v in flows.src),
        flow_dst=tuple(int(v) for v in flows.dst),
        flow_t_start=tuple(float(v) for v in flows.t_start),
        flow_t_stop=tuple(float(v) for v in flows.t_stop),
        flow_volume=tuple(float(v) for v in flows.volume),
        flow_rate=tuple(float(v) for v in flows.rate),
        flow_nic_buffer=tuple(float(v) for v in flows.nic_buffer))


def ref_run(config: dict, scheme: str, over: dict, flows: tr.Flows) -> ref.Run:
    g = params_with(config, over)
    return ref.Run(fabric=config["fabric"], roll=flows.roll,
                   src=flows.src, dst=flows.dst, t_start=flows.t_start,
                   t_stop=flows.t_stop, volume=flows.volume, rate=flows.rate,
                   nic_buffer=flows.nic_buffer,
                   scheme=tuple(config["schemes"][scheme]), link=g["link"],
                   dcqcn=g["dcqcn"], rev=g["rev"], dt=float(g["sim"]["dt"]))


TRACE_FIELDS = ("delivered", "rate", "inst_thr", "marked", "cnp", "ctrl",
                "max_q", "n_paused", "pause_time")
FINAL_FIELDS = ("qh", "nicq", "delivered", "offered", "dropped", "est", "rate",
                "rp_target", "alpha", "byte_cnt", "tmr", "alpha_tmr", "bc_stage",
                "t_stage", "hold", "np_tmr", "paused")


def program_view(sim) -> dict:
    """A program ``SimResult`` as the reference's dict of numpy arrays."""
    trace = {k: np.asarray(getattr(sim, k)) for k in TRACE_FIELDS}
    final = {k: np.asarray(getattr(sim.final, k)) for k in FINAL_FIELDS}
    return dict(trace=trace, final=final)


def digest(views) -> str:
    h = hashlib.sha1()
    for v in views:
        for part in ("trace", "final"):
            for k in sorted(v[part]):
                h.update(np.ascontiguousarray(v[part][k]).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


BYTE_FIELDS = ("nicq", "delivered", "offered", "dropped", "qh")
RATE_FIELDS = ("est", "rate", "rp_target", "alpha")
TIMER_FIELDS = ("tmr", "alpha_tmr", "hold", "np_tmr")          # s
STAGE_FIELDS = ("bc_stage", "t_stage")                         # counts


def _final_gaps(p: dict, r: dict, F: int, offered: float, config: dict) -> dict:
    """The final state's gaps (see ``compare``) and each field's own."""
    line = float(config["link"]["line_rate"])
    dt = float(config["sim"]["dt"])
    norm = dict({k: offered for k in BYTE_FIELDS}, qh=float(config["link"]["port_buffer"]),
                alpha=1.0, **{k: line for k in RATE_FIELDS if k != "alpha"})

    def pair(k):
        a = np.asarray(p[k], np.float64)
        b = np.asarray(r[k], np.float64)
        return (a[:F] if a.ndim == 1 else a[:F, :b.shape[1]]), b

    by_field = {}
    for k, n in norm.items():
        a, b = pair(k)
        by_field[k] = float(np.abs(a - b).max() / n)
    flipped = np.zeros(F, bool)
    for k in TIMER_FIELDS:
        a, b = pair(k)
        flipped |= np.abs(a - b) >= dt / 2
    tol = dict(byte_cnt=line * dt / 2, **{k: 0.5 for k in STAGE_FIELDS})
    for k, t in tol.items():
        a, b = pair(k)
        flipped |= np.abs(a - b) >= t
    # the program keeps a flag for every link of the fabric, the
    # reference one for every link a flow crosses: compare the counts
    n_pa, n_pb = (int((np.asarray(x["paused"]) > 0.5).sum()) for x in (p, r))
    return dict(final_bytes_gap=max(by_field[k] for k in BYTE_FIELDS),
                final_rate_gap=max(by_field[k] for k in RATE_FIELDS),
                phase_flips=(int(flipped.sum()) + abs(n_pa - n_pb)) / (F + len(r["paused"])),
                by_field=by_field)


GAPS = ("delivered_gap", "total_gap", "rate_gap", "queue_gap", "event_gap",
        "final_bytes_gap", "final_rate_gap", "phase_flips")


def compare(prog: list, refs: list, config: dict) -> dict:
    """Gaps between the program's runs and the reference's, run by run;
    each number is the widest over the runs.

    * ``delivered_gap``: widest gap in any flow's cumulative delivered
      bytes at any trace sample, as a share of the run's largest final
      delivered volume;
    * ``total_gap``: widest gap in the run's total delivered bytes at any
      trace sample, as a share of its final total;
    * ``rate_gap``: widest gap in any flow's traced injection rate, as a
      share of line rate;
    * ``queue_gap``: widest gap in the traced hottest queue, as a share
      of the port buffer;
    * ``event_gap``: widest relative gap in the run's total marks, CNPs,
      notifications and pause time;
    * ``final_bytes_gap``: widest gap in the final per-flow bytes (NIC and
      hop queues, delivered, offered, dropped), as a share of the run's
      largest offered volume (hop queues: of the port buffer);
    * ``final_rate_gap``: widest gap in the final per-flow rates
      (crossing estimate, rate, target; share of line rate) and DCQCN
      alpha;
    * ``phase_flips``: share of the run's flows and links whose final
      phase state (timers, byte counter, increase stages, pause flag)
      differs by a step or more: an event landing on another step.

    ``final_fields`` (reported only) splits the final gaps by field."""
    line = float(config["link"]["line_rate"])
    port = float(config["link"]["port_buffer"])
    dt = float(config["sim"]["dt"])
    out = dict.fromkeys(GAPS, 0.0)
    worst = dict(out)
    fields: dict = {}
    for i, (p, r) in enumerate(zip(prog, refs)):
        pt, rt = p["trace"], r["trace"]
        F = rt["delivered"].shape[1]
        g = lambda k: np.asarray(pt[k], np.float64)[..., :F] if np.ndim(pt[k]) > 1 \
            else np.asarray(pt[k], np.float64)  # noqa: E731
        rr = lambda k: np.asarray(rt[k], np.float64)  # noqa: E731
        scale = max(rr("delivered")[-1].max(), 1.0)
        total = rr("delivered").sum(axis=1)
        gaps = dict(
            delivered_gap=np.abs(g("delivered") - rr("delivered")).max() / scale,
            total_gap=np.abs(g("delivered").sum(axis=1) - total).max() / max(total[-1], 1.0),
            rate_gap=np.abs(g("rate") - rr("rate")).max() / line,
            queue_gap=np.abs(g("max_q") - rr("max_q")).max() / port)
        ev = [abs(g(k).sum() - rr(k).sum()) / max(rr(k).sum(), 1.0)
              for k in ("marked", "cnp", "ctrl")]
        ev.append(abs(g("pause_time").sum() - rr("pause_time").sum())
                  / max(rr("pause_time").sum(), dt))
        gaps["event_gap"] = max(ev)
        offered = max(float(np.asarray(r["final"]["offered"]).max()), scale)
        fin = _final_gaps(p["final"], r["final"], F, offered, config)
        for k, v in fin.pop("by_field").items():
            fields[k] = max(fields.get(k, 0.0), v)
        gaps.update(fin)
        for k, v in gaps.items():
            if v > out[k]:
                out[k], worst[k] = float(v), i
    out["worst_run"] = worst
    out["final_fields"] = fields
    return out


def reference_check(points_prog: list, points_ref: list, config: dict,
                    n_steps: int, trace_every: int, dtype: str = "float32") -> dict:
    refs = ref.simulate(points_ref, n_steps, trace_every, dtype)
    return compare(points_prog, refs, config)


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``bench/run.py``."""

    e2e: dict                 # end-to-end metric values
    ctx: dict                 # what the per-layer readers read
    compared: dict            # name -> (value, limit)
    attempted: int
    failed: int
    notes: list               # lines for standard error
