"""`--scale`: fabric-family scaling matrix for the repro.net subsystem.

For each (fabric, workload) cell: time the route-table build (+ its
validity check, both cached per fabric afterwards), then run a 3-scheme
Sweep as one jitted launch and report wall time and simulated
steps/second.  Every invocation appends a record to ``BENCH_net.json``
at the repo root so the perf trajectory accumulates across commits.

The routing matrix rides along: the adversarial group-shift dragonfly
cell sweeps {min, valiant, ugal} x all schemes in ONE launch and
records delivered bytes per (scheme, routing) — the record asserts the
paper-level ordering ``ugal >= min`` on that pattern.

So does the PFC-pathology leg: the HoL-victim scenario runs the three
paper schemes and records ``victim_slowdown`` / ``pause_s`` per scheme.
The run fails unless Rev spares the victim better than DCQCN, which
beats PFC-only (the paper's ordering), and — when the committed
``BENCH_net.json`` already carries a ``pfc_pathology`` record — unless
the Rev-vs-DCQCN margin stays within half of that baseline (the CI
``pfc-pathology`` job's gate).

    PYTHONPATH=src python benchmarks/run.py --scale            # full
    PYTHONPATH=src python benchmarks/run.py --scale --quick    # CI-sized
"""

from __future__ import annotations

import json
import os
import time

import numpy as np


BENCH_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_net.json")


def _matrix(quick: bool):
    from repro.core.workloads import all_to_all, incast_storm
    from repro.net import FabricSpec

    def storm(n):
        return incast_storm(max(4, n // 4), max(1, n // 16), n,
                            volume=1e6, t_start=0.0)

    def a2a(n):
        return all_to_all(n, 0.5e6, phases=4, nodes=range(min(n, 16)))

    cells = [
        ("clos64", FabricSpec.clos3(4), storm),
        ("ft64_2to1", FabricSpec.fat_tree(4, taper=2), storm),
        ("dfly72", FabricSpec.dragonfly(a=4, p=2, h=2), a2a),
    ]
    if not quick:
        cells += [
            ("clos512", FabricSpec.clos3(8), storm),
            ("xgft4lvl", FabricSpec.xgft((4, 2, 2, 2), (1, 2, 2, 2)),
             a2a),
            ("ft216_3to1", FabricSpec.xgft((6, 6, 6), (1, 2, 6)), storm),
            ("dfly342", FabricSpec.dragonfly(a=6, p=3, h=3), a2a),
        ]
    return cells


def run_matrix(quick: bool = False, n_steps: int = 600) -> list[dict]:
    from repro.core import CCScheme, PAPER_CONFIG, Sweep
    from repro.net import validate_table

    cfg = PAPER_CONFIG
    records = []
    for name, fab, wl_fn in _matrix(quick):
        t0 = time.perf_counter()
        topo = fab.build(cfg.link.line_rate)
        table = fab.route_table()                     # validated in cache
        table_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        validate_table(topo, table)                   # re-check, timed
        validate_s = time.perf_counter() - t0
        spec = wl_fn(topo.n_nodes).spec(fabric=fab)
        scn = spec.build(cfg)          # built once; the timed region
        t0 = time.perf_counter()       # below times only the sweep
        sweep = Sweep.grid(
            configs={s.name: cfg.replace(scheme=s) for s in CCScheme},
            scenarios={name: scn})
        res = sweep.run(n_steps=n_steps)
        sweep_s = time.perf_counter() - t0
        sim_steps = 3 * n_steps                       # 3 schemes batched
        records.append({
            "name": name,
            "fabric": fab.name,
            "n_nodes": int(topo.n_nodes),
            "n_switches": int(topo.n_switches),
            "n_links": int(topo.n_links),
            "h_max": int(table.h_max),
            "n_flows": int(scn.routes.shape[0]),
            "table_s": round(table_s, 4),
            "validate_s": round(validate_s, 4),
            "sweep_s": round(sweep_s, 3),
            "sim_steps_per_s": round(sim_steps / max(sweep_s, 1e-9), 1),
            "delivered_mb": round(float(np.asarray(
                res[f"DCQCN_REV/{name}"].final.delivered).sum()) / 1e6, 3),
        })
    return records


def run_routing_matrix(quick: bool = False, n_steps: int = 1200) -> dict:
    """Routing-mode axis on the adversarial dragonfly: one Sweep of
    {min, valiant, ugal} x 3 schemes on group-shift traffic."""
    from repro.core import CCScheme, PAPER_CONFIG, Sweep
    from repro.core.workloads import group_shift
    from repro.net import FabricSpec

    cfg = PAPER_CONFIG
    if quick:
        fab, n_steps = FabricSpec.dragonfly(a=2, p=2, h=2), 600
    else:
        fab = FabricSpec.dragonfly(a=4, p=2, h=2)
    g = fab.a * fab.h + 1 if fab.groups is None else fab.groups
    hpg = fab.a * fab.p
    spec = group_shift(g, hpg, t_stop=n_steps * cfg.sim.dt).spec(
        fabric=fab, n_paths=4, label="adv")
    t0 = time.perf_counter()
    rset = fab.route_set(4)                       # timed: K-path build
    set_s = time.perf_counter() - t0
    configs = {f"{s.name}/{r}": cfg.replace(scheme=s, routing=r)
               for s in CCScheme for r in ("min", "valiant", "ugal")}
    t0 = time.perf_counter()
    res = Sweep.grid(configs=configs, scenarios={"adv": spec}).run(
        n_steps=n_steps)
    sweep_s = time.perf_counter() - t0
    delivered = {
        name: round(float(np.asarray(r.final.delivered).sum()) / 1e6, 3)
        for name, r in res.items()}
    ugal_ge_min = all(
        delivered[f"{s.name}/ugal/adv"] >= delivered[f"{s.name}/min/adv"]
        for s in CCScheme)
    return {
        "name": "dfly_adv_routing",
        "fabric": fab.name,
        "workload": spec.label,
        "k_paths": int(rset.k_paths),
        "route_set_s": round(set_s, 4),
        "n_points": len(res),
        "sweep_s": round(sweep_s, 3),
        "sim_steps_per_s": round(len(res) * n_steps / max(sweep_s, 1e-9),
                                 1),
        "delivered_mb": delivered,
        "ugal_ge_min": bool(ugal_ge_min),
    }


def run_pathology_matrix(quick: bool = False, n_steps: int = 5000) -> dict:
    """Victim-flow leg: the HoL-victim scenario x the three paper
    schemes as one launch.  Records ``victim_slowdown`` / ``pause_s``
    per scheme plus the ordering verdict the paper stakes its HoL
    claim on (Rev spares the victim, DCQCN collaterally marks it,
    PFC-only head-of-line blocks it)."""
    from repro.core import CCSpec, Sweep
    from repro.core.workloads import hol_victim_incast
    from repro.net import FabricSpec

    specs = {
        "PFC_ONLY": CCSpec(marking="cp", notification="np",
                           reaction="pfc"),
        "DCQCN": CCSpec(marking="cp", notification="np", reaction="rp"),
        "DCQCN_REV": CCSpec(marking="ecp", notification="enp",
                            reaction="erp"),
    }
    spec = hol_victim_incast(4, 64).spec(fabric=FabricSpec.clos3(4))
    t0 = time.perf_counter()
    res = Sweep.grid(configs=specs, scenarios={"hol": spec}).run(
        n_steps=n_steps)
    sweep_s = time.perf_counter() - t0
    vic = {s: round(float(res[f"{s}/hol"].victim_slowdown()), 4)
           for s in specs}
    pause = {s: round(float(res[f"{s}/hol"].pause_duration()), 6)
             for s in specs}
    return {
        "name": "pfc_pathology",
        "fabric": "clos64",
        "workload": spec.label,
        "n_steps": int(n_steps),
        "n_points": len(res),
        "sweep_s": round(sweep_s, 3),
        "victim_slowdown": vic,
        "pause_s": pause,
        "rev_beats_dcqcn": bool(
            vic["DCQCN_REV"] < vic["DCQCN"] < vic["PFC_ONLY"]),
    }


def pathology_baseline(path: str = BENCH_PATH) -> "dict | None":
    """Most recent committed ``pfc_pathology`` record, if any."""
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
    except (json.JSONDecodeError, OSError):
        return None
    for run_ in reversed(doc.get("runs", [])):
        for r in reversed(run_.get("records", [])):
            if r.get("name") == "pfc_pathology":
                return r
    return None


def append_bench_record(records: list[dict], path: str = BENCH_PATH) -> None:
    doc = {"runs": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (json.JSONDecodeError, OSError):
            pass
    try:
        from ._env import bench_env
    except ImportError:              # `python benchmarks/net_scale.py`
        from _env import bench_env
    doc.setdefault("runs", []).append({
        "unix_time": int(time.time()),
        **bench_env(),
        "records": records,
    })
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def main(quick: bool = False) -> list[tuple]:
    records = run_matrix(quick=quick)
    routing = run_routing_matrix(quick=quick)
    records.append(routing)
    baseline = pathology_baseline()        # before this run appends
    pathology = run_pathology_matrix(quick=quick)
    records.append(pathology)
    append_bench_record(records)
    rows = []
    for r in records[:-2]:      # the fabric cells; routing + pathology
        # records carry their own row formats below
        rows.append((
            f"net_scale.{r['name']}", r["sweep_s"] * 1e6,
            f"N={r['n_nodes']} L={r['n_links']} F={r['n_flows']} "
            f"H={r['h_max']} table={r['table_s']:.2f}s "
            f"{r['sim_steps_per_s']:.0f} steps/s"))
    mins = sum(v for k, v in routing["delivered_mb"].items() if "/min/" in k)
    ugal = sum(v for k, v in routing["delivered_mb"].items()
               if "/ugal/" in k)
    rows.append((
        f"net_scale.{routing['name']}", routing["sweep_s"] * 1e6,
        f"{routing['n_points']}pt {routing['fabric']} "
        f"min={mins:.1f}MB ugal={ugal:.1f}MB "
        f"ugal_ge_min={routing['ugal_ge_min']}"))
    if not routing["ugal_ge_min"]:
        raise AssertionError(
            f"UGAL under-delivered vs minimal routing on the adversarial "
            f"pattern: {routing['delivered_mb']}")
    vic = pathology["victim_slowdown"]
    rows.append((
        f"net_scale.{pathology['name']}", pathology["sweep_s"] * 1e6,
        f"{pathology['n_points']}pt {pathology['workload']} "
        f"vic REV={vic['DCQCN_REV']:.3f} DCQCN={vic['DCQCN']:.3f} "
        f"PFC={vic['PFC_ONLY']:.3f} ordered={pathology['rev_beats_dcqcn']}"))
    if not pathology["rev_beats_dcqcn"]:
        raise AssertionError(
            f"victim ordering violated (want REV < DCQCN < PFC_ONLY): "
            f"{vic}")
    if baseline is not None:
        want = (baseline["victim_slowdown"]["DCQCN"]
                - baseline["victim_slowdown"]["DCQCN_REV"])
        got = vic["DCQCN"] - vic["DCQCN_REV"]
        if got < 0.5 * want:
            raise AssertionError(
                f"Rev's victim-protection margin regressed vs the "
                f"committed baseline: {got:.4f} < 0.5 * {want:.4f}")
    rows.append(("net_scale.bench_json", 0.0, BENCH_PATH))
    return rows


if __name__ == "__main__":
    for row in main():
        print(",".join(str(x) for x in row))
