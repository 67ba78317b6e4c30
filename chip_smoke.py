#!/usr/bin/env python3
"""Bring-up smoke run of the fluid simulator on a TPU.

Drives the main path once, through the entry points a user calls
(``Sweep``, ``CCQueryEngine``, ``run_fleet``), and checks what comes
out.  Each phase prints its own lines; the last line of standard output
is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failure prints its traceback and exits non-zero with no such line,
and so does a run that finds no TPU: this never falls back to the CPU.

    python3 chip_smoke.py             # one chip, every phase
    python3 chip_smoke.py --chips 4   # four chips: the run-sharded sweep
                                      # and its one-device reference only

One-chip phases, in order:

  device      platform, kind, count, JAX version, compile-cache dir
  paper       the paper's scenes x {PFC_ONLY, DCQCN, DCQCN_REV} in one
              launch: the §II orderings, the ``reduce="scat"`` engine
              against the default, and the frozen CPU golden grids
  real_size   a 1000-host fat tree under a 64-host all-to-all (4032
              flows) mixed with a 256-to-8 incast storm (256 flows)
              x the three schemes in one launch: host build, compile,
              and a warm launch split into host staging, execution and
              fetch; peak device memory, sanity, and the CC counters
              of each scheme (the storm must engage the loop)
  whatif      8 queries over 2 shape signatures through
              ``CCQueryEngine``, each against a standalone ``Sweep.run``
  fleet       the paper grid through ``run_fleet`` (2 threads) against
              its single launch

The seconds printed are timings of one smoke run, not benchmark
numbers.  Compiles go to JAX's persistent cache
(``benchmarks/_env.use_compile_cache``), so a second run in the same
checkout compiles faster; the ``compile_s_total`` line shows it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks._env import use_compile_cache  # noqa: E402
from repro.core import (SWEEP_EXEC_CACHE, CCSpec, ScenarioSpec,  # noqa: E402
                        Sweep, config_grid, obs)
from repro.core.experiments import _sweep_executable  # noqa: E402
from repro.core.workloads import (all_to_all, concat,  # noqa: E402
                                  hol_victim_incast, incast_storm)
from repro.dist import sweep_mesh  # noqa: E402
from repro.fleet import FleetConfig, run_fleet  # noqa: E402
from repro.net import FabricSpec  # noqa: E402
from repro.serve.whatif import (Admitted, CCQueryEngine,  # noqa: E402
                                EngineConfig, WhatIfQuery)

#: the paper's three schemes as stage triples
SCHEMES = {
    "PFC_ONLY": CCSpec(marking="cp", notification="np", reaction="pfc"),
    "DCQCN": CCSpec(marking="cp", notification="np", reaction="rp"),
    "DCQCN_REV": CCSpec(marking="ecp", notification="enp", reaction="erp"),
}

#: the golden suites' float tolerance (tests/test_golden.py)
RTOL = 2e-3

#: the real-size grid: a 1000-host 3-level fat tree (10-ary), with an
#: all-to-all among 64 hosts spread over it (4 phases, 1 MB per pair)
#: and a 32:1 incast storm from 0.5 to 2.5 ms.  The all-to-all alone
#: shares every NIC fairly and never builds a queue; the storm is what
#: makes the three schemes mark, notify and pause differently.
REAL_FABRIC = FabricSpec.fat_tree(10)
REAL_HOSTS = 1000
REAL_GROUP = 64
REAL_STORM = (256, 8)
REAL_STEPS = 3000

#: DCQCN marking thresholds of the four-chip grid (kmin <= kmax = 15 KiB)
KMINS = (3840.0, 7680.0, 11520.0, 15360.0)
#: the four-chip grid runs fewer steps: its one-device reference holds
#: all 12 runs on one chip.  The storm opens at step 500, so the last
#: 200 steps are where the runs part ways.
FOUR_CHIP_STEPS = 700

WHATIF_STEPS = 2000


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------


def _arrays(res) -> list[np.ndarray]:
    """Every array of a ``SimResult`` or ``SweepResult``: the time base,
    each trace field and the whole final state."""
    traces = res.traces if hasattr(res, "traces") else [
        res.delivered, res.rate, res.inst_thr, res.max_q, res.n_paused,
        res.marked, res.cnp, res.n_nonmin, res.ctrl, res.pause_time,
        res.vc_stall]
    return [np.asarray(x) for x in
            (res.times, *[t for t in traces if t is not None],
             *jax.tree.leaves(res.final))]


def bitwise(a, b) -> bool:
    xa, xb = _arrays(a), _arrays(b)
    return len(xa) == len(xb) and all(
        x.dtype == y.dtype and x.shape == y.shape
        and x.tobytes() == y.tobytes() for x, y in zip(xa, xb))


def _rel(a: float, b: float) -> float:
    if np.isnan(a) and np.isnan(b):
        return 0.0
    if np.isnan(a) or np.isnan(b):
        return float("inf")
    return abs(a - b) / max(abs(a), abs(b), 1e-30)


def summary_diffs(sa: dict, sb: dict) -> dict[str, float]:
    """Largest relative difference of each ``summary()`` field over the
    points of two ``SweepResult.summary()`` dicts."""
    out: dict[str, float] = {}
    for name, row in sa.items():
        for k, v in row.items():
            w = sb[name][k]
            if v is None or w is None:
                d = 0.0 if v is w else float("inf")
            else:
                d = max(_rel(float(x), float(y)) for x, y in
                        zip(np.atleast_1d(v), np.atleast_1d(w)))
            out[k] = max(out.get(k, 0.0), d)
    return out


def compare_engines(phase: str, label: str, ref, got) -> None:
    """Print bitwise equality and per-field differences of ``got`` vs
    the default engine's ``ref``; the headline floats must agree within
    the golden tolerance."""
    same = bitwise(ref, got)
    diffs = summary_diffs(ref.summary(), got.summary())
    say(phase, f"{label} vs default: bitwise={same} max_rel_diff "
        + " ".join(f"{k}={v!r}" for k, v in diffs.items()))
    for k in ("aggregate_gbps", "completion_ms", "delivered_mb",
              "peak_queue_kb", "victim_slowdown"):
        check(diffs[k] <= RTOL, f"{label} {k} within rtol {RTOL} of the "
              f"default engine (got {diffs[k]!r})")


def golden_diffs(got: dict, want: dict, float_keys, count_keys) -> list:
    """Out-of-tolerance entries, by tests/test_golden.py's rule: floats
    within rtol 2e-3 (NaN matches NaN), counters within 2% or 2."""
    bad = []
    for name, w in want.items():
        g = got[name]
        for k in float_keys:
            if np.isnan(w[k]):
                ok = np.isnan(g[k])
            else:
                ok = abs(g[k] - w[k]) <= 1e-9 + RTOL * abs(w[k])
            if not ok:
                bad.append((name, k, g[k], w[k]))
        for k in count_keys:
            if abs(g[k] - w[k]) > max(2, 0.02 * w[k]):
                bad.append((name, k, g[k], w[k]))
    return bad


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(n_chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", f"a TPU backend (found {d.platform!r}); "
          f"this smoke run never falls back to another device")
    check(len(devs) >= n_chips, f"{n_chips} chips (found {len(devs)})")
    cache = use_compile_cache()
    say("device", f"platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} compile_cache={cache}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def paper_sweep() -> Sweep:
    return Sweep.grid(SCHEMES, {
        "roll0": ScenarioSpec.paper_incast(roll=0),
        "roll1": ScenarioSpec.paper_incast(roll=1),
        "holvictim": hol_victim_incast(4, 64).spec(
            fabric=FabricSpec.clos3(4)),
    })


def phase_paper():
    p = "paper"
    sweep = paper_sweep()
    res = sweep.run()
    s = res.summary()
    ct = {k: s[f"{k}/roll0"]["completion_ms"] for k in SCHEMES}
    victim = float(res["DCQCN_REV/roll0"].mean_throughput_while_active()[4])
    vs = {k: s[f"{k}/holvictim"]["victim_slowdown"] for k in SCHEMES}
    say(p, f"{len(res)} points, {res.times.size} samples; roll0 "
        f"completion_ms={ct}; DCQCN_REV roll0 victim="
        f"{victim / 1e9!r} GB/s; holvictim victim_slowdown={vs}")
    check(ct["DCQCN_REV"] < ct["PFC_ONLY"] < ct["DCQCN"],
          "roll0 completion REV < PFC_ONLY < DCQCN")
    check(abs(victim / 1e9 - 5.7) < 0.3, "REV roll0 victim ~ 5.7 GB/s")
    check(vs["DCQCN_REV"] < vs["DCQCN"] < vs["PFC_ONLY"],
          "victim_slowdown REV < DCQCN < PFC_ONLY")

    compare_engines(p, 'reduce="scat"', res, sweep.run(reduce="scat"))

    import test_golden as tg
    for label, run, path, fk, ck in (
            ("routing_sweep", tg.current_summaries, tg.GOLDEN_PATH,
             tg.FLOAT_KEYS, tg.COUNT_KEYS),
            ("pfc_pathology", tg.pathology_summaries, tg.PATHOLOGY_PATH,
             tg.PATHOLOGY_FLOAT_KEYS, tg.PATHOLOGY_COUNT_KEYS)):
        with open(path) as f:
            want = json.load(f)["summaries"]
        got = run()
        check(set(got) == set(want), f"{label}: golden point set")
        bad = golden_diffs(got, want, fk, ck)
        worst = summary_diffs({n: {k: got[n][k] for k in fk} for n in want},
                              {n: {k: want[n][k] for k in fk}
                               for n in want})
        say(p, f"golden {label}: {len(want)} points, "
            f"{len(bad)} outside tolerance; max_rel_diff "
            + " ".join(f"{k}={v!r}" for k, v in worst.items()))
        for name, k, g, w in bad:
            say(p, f"  {label} {name}.{k}: chip {g!r} golden {w!r}")
        check(not bad, f"{label} matches the CPU golden")
    return sweep, res


def real_size_sweep(configs: dict) -> tuple[Sweep, float]:
    """The real-size grid over ``configs``, and its host build seconds.
    The scenario is built once: every config shares its link and time
    step, so the routed tensors are the same for all."""
    t0 = time.perf_counter()
    nodes = [i * REAL_HOSTS // REAL_GROUP for i in range(REAL_GROUP)]
    wl = concat(all_to_all(REAL_HOSTS, 1e6, phases=4, nodes=nodes),
                incast_storm(*REAL_STORM, REAL_HOSTS, t_start=0.5e-3,
                             t_stop=2.5e-3))
    scn = wl.spec(fabric=REAL_FABRIC).build(next(iter(configs.values())))
    sweep = Sweep.grid(configs, {"a2a": scn})
    return sweep, time.perf_counter() - t0


def _timed_run(sweep: Sweep, **kw):
    """(result, compile s, wall s) of one launch."""
    before = SWEEP_EXEC_CACHE.stats()
    t0 = time.perf_counter()
    res = sweep.run(**kw)
    wall = time.perf_counter() - t0
    return res, (SWEEP_EXEC_CACHE.stats() - before).build_s, wall


def _split_launch(sweep: Sweep, n_steps: int):
    """One warm launch of ``sweep`` and the seconds of the three parts
    ``Sweep.run`` chains, read from its spans: host staging (stack, pad,
    initial state; device work it queued finishes in the next part),
    execution until the device is done, and the fetch of traces and
    final state."""
    before = obs.stats()
    res = sweep.run(n_steps=n_steps)
    spans = obs.stats() - before
    return res, tuple(spans.span(f"repro.sweep.{part}").s
                      for part in ("stage", "execute", "fetch"))


def phase_real_size():
    p = "real_size"
    sweep, build_s = real_size_sweep(SCHEMES)
    scn = sweep.points[0].scenario
    say(p, f"fabric={REAL_FABRIC.name} hosts={REAL_HOSTS} "
        f"flows={scn.routes.shape[0]} links={scn.capacity.shape[0]} "
        f"runs={len(sweep.points)} steps={REAL_STEPS}")
    res, compile_s, cold_s = _timed_run(sweep, n_steps=REAL_STEPS)
    again, (prep_s, exec_s, fetch_s) = _split_launch(sweep, REAL_STEPS)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    say(p, f"smoke timing, not a benchmark: host_build_s={build_s!r} "
        f"compile_s={compile_s!r} first_launch_s={cold_s!r} (incl. "
        f"compile); warm launch: host_prepare_s={prep_s!r} "
        f"execute_s={exec_s!r} fetch_s={fetch_s!r} "
        f"peak_bytes_in_use={peak}")
    check(bitwise(again, res), "a repeated launch is bitwise identical")
    arrays = _arrays(res)
    check(all(np.isfinite(a).all() for a in arrays
              if np.issubdtype(a.dtype, np.floating)),
          "every trace and final-state value is finite")
    fin = res.final
    # both are f32 running sums of per-step increments, so a finished
    # flow's two totals may differ by their rounding: at most n_steps
    # half-ulps of the total for a sequential sum
    delivered = np.asarray(fin.delivered, np.float64)
    offered = np.asarray(fin.offered, np.float64)
    excess = float(np.max((delivered - offered) / np.maximum(offered, 1.0)))
    bound = REAL_STEPS * float(np.finfo(np.float32).eps) / 2
    say(p, f"largest (delivered - offered) / offered over all flows: "
        f"{excess!r} (f32 rounding bound {bound!r})")
    check(excess <= bound, "delivered <= offered for every flow, up to "
          "the rounding of their f32 sums")
    check(min(float(np.asarray(fin.qh).min()),
              float(np.asarray(fin.nicq).min()),
              float(np.asarray(res.traces.max_q).min())) >= 0.0,
          "no queue ever negative")
    s = {k.split("/")[0]: v for k, v in res.summary().items()}
    for k, v in s.items():
        say(p, f"{k}: " + " ".join(
            f"{f}={v[f]!r}" for f in ("delivered_mb", "marks", "cnps",
                                      "pause_s", "peak_queue_kb")))
    check(all(v["marks"] > 0 and v["cnps"] > 0 for v in s.values()),
          "the storm is marked and notified under every scheme")
    check(s["PFC_ONLY"]["pause_s"] > s["DCQCN"]["pause_s"]
          > s["DCQCN_REV"]["pause_s"],
          "pause time PFC_ONLY > DCQCN > DCQCN_REV")


def phase_whatif() -> None:
    p = "whatif"
    cfgs = {"rev": SCHEMES["DCQCN_REV"], "dcqcn": SCHEMES["DCQCN"],
            "pfc": SCHEMES["PFC_ONLY"],
            "dcqcn-kmin": config_grid(
                SCHEMES["DCQCN"], **{"dcqcn.kmin": [7680.0]})["kmin=7680"]}
    scenarios = {   # two shape signatures: different fabrics
        "hol": ScenarioSpec.paper_incast(roll=0),
        "ft-perm": ScenarioSpec.permutation(
            64, fabric=FabricSpec.fat_tree(4), t_start=0.0),
    }
    eng = CCQueryEngine(EngineConfig(max_batch=4))
    tickets = {}
    for sn, spec in scenarios.items():
        for cn, cfg in cfgs.items():
            out = eng.submit(WhatIfQuery(cfg=cfg, scenario=spec,
                                         n_steps=WHATIF_STEPS,
                                         label=f"{cn}/{sn}"))
            check(isinstance(out, Admitted), f"query admitted ({out})")
            tickets[out.ticket] = (cn, sn)
    eng.drain()
    m = eng.metrics()
    say(p, f"{m['queries']} queries, {m['batches']} batches, "
        f"{m['signatures']} signatures; exec_cache misses="
        f"{m['exec_cache']['misses']} hits={m['exec_cache']['hits']} "
        f"compile_s={m['compile_s']!r}")
    check(m["queries"] == 8 and m["signatures"] == 2, "8 queries, 2 shapes")
    check(m["exec_cache"]["misses"] == 2, "one compile per signature")
    same = 0
    for ticket, (cn, sn) in tickets.items():
        want = Sweep([("p", cfgs[cn], scenarios[sn])]).run(
            n_steps=WHATIF_STEPS)["p"]
        same += bitwise(eng.result(ticket).result, want)
    say(p, f"{same}/{len(tickets)} answers bitwise equal to a "
        f"standalone Sweep.run")
    check(same == len(tickets), "every answer equals its standalone run")


def phase_fleet(sweep: Sweep, ref) -> None:
    p = "fleet"
    out = run_fleet(sweep, config=FleetConfig(n_workers=2))
    st = out.stats
    same = bitwise(ref, out.result)
    say(p, f"shards={st.n_shards} executed={st.executed} "
        f"compiles={st.compiles} abandoned={st.abandoned} "
        f"bitwise_vs_single_launch={same}")
    check(st.compiles == 1, "the fleet compiled once")
    check(same, "the fleet merge equals the single launch")


def phase_four_chips() -> None:
    """The run axis sharded over four chips against one device."""
    p = "four_chips"
    configs = {}
    for name, spec in SCHEMES.items():
        for key, cfg in config_grid(spec, **{"dcqcn.kmin": KMINS}).items():
            configs[f"{name}/{key}"] = cfg
    sweep, build_s = real_size_sweep(configs)
    mesh = sweep_mesh(4)
    say(p, f"runs={len(sweep.points)} "
        f"flows={sweep.points[0].scenario.routes.shape[0]} "
        f"steps={FOUR_CHIP_STEPS} mesh={dict(mesh.shape)} "
        f"host_build_s={build_s!r}")
    t0 = time.perf_counter()
    sharded = sweep.run(n_steps=FOUR_CHIP_STEPS, mesh=mesh)
    t_sharded = time.perf_counter() - t0
    t0 = time.perf_counter()
    single = sweep.run(n_steps=FOUR_CHIP_STEPS)
    t_single = time.perf_counter() - t0
    say(p, f"smoke timing, not a benchmark: sharded_s={t_sharded!r} "
        f"single_device_s={t_single!r} (both incl. compile)")
    # runs that all came out alike would hide a shard landing in the
    # wrong place
    distinct = len({np.asarray(r).tobytes() for r in single.final.delivered})
    say(p, f"{distinct} distinct per-flow delivered vectors over "
        f"{len(sweep.points)} runs")
    check(distinct > 1, "the runs differ from each other")
    # the device arrays Sweep.run pulls to the host: run the executable
    # it resolved once more and look at where the output lives
    static, args, _, _ = sweep._prepare(FOUR_CHIP_STEPS, mesh=mesh)
    _, traces = _sweep_executable(static, args)(*args)
    out = traces.delivered
    held = sorted({s.device.id for s in out.addressable_shards})
    say(p, f"output sharding={out.sharding} devices_holding_runs={held} "
        f"shard_shape={out.addressable_shards[0].data.shape}")
    check(len(held) == 4, "all four devices hold runs")
    same = bitwise(single, sharded)
    say(p, f"sharded vs single-device: bitwise={same}")
    check(same, "the sharded sweep equals the one-device sweep")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded sweep and "
                         "its one-device reference")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    device = phase_device(args.chips)
    if args.chips == 4:
        phase_four_chips()
    else:
        paper, paper_res = phase_paper()
        phase_real_size()
        phase_whatif()
        phase_fleet(paper, paper_res)
    say("done", f"compile_s_total={SWEEP_EXEC_CACHE.stats().build_s!r} "
        f"wall_s={time.perf_counter() - t0!r}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
