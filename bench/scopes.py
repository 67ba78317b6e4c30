"""Put a profiler trace's device and idle time down to the program's
phases and spans.

The program names the phases of its step with ``obs.scope`` (``fluid.*``
in each op's HLO metadata) and its host work with ``obs.span``
(``repro.*`` host events on the device trace's clock).  Given the
program's ``{module: {instruction: scope}}`` map
(``repro.core.obs.sweep_op_scopes()``), ``reduce_scoped`` returns every
key of ``bench.trace.reduce``, unchanged, and adds:

* ``scope_s``: the self time of each scope, over the device ops that lie
  inside an ``XLA Modules`` event of a module the map names; ops of
  other modules (staging's eager ops, whose names such as ``%copy.1``
  recur across modules) count as ``"other_module"``;
* ``span_s``: ``[count, seconds]`` of each ``repro.*`` host span inside
  the traced window;
* ``op_gaps_by_scope``: idle seconds in gaps under 12 ms, keyed by the
  scope of the op that ends where the gap starts;
* ``idle_by_span``: idle seconds in gaps of 1 ms or more, keyed by the
  innermost ``bench.*`` or ``repro.*`` host span open at the gap's
  middle, and ``idle_gaps_by_span``, the longest such gaps;
* ``device_ops_scoped``: ``device_ops`` with each op's scope in brackets,
  and ``unscoped_ops``, the ops that took most time outside any scope.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s> [--out <file>]

runs the cell traced, as ``bench/run.py --trace 1`` does, keeps its
trace, and prints one JSON line: the result line, the scoped reduction,
and per simulated step the device time of the link reductions, of the
per-flow CC block and of the trace decimation.
"""

from __future__ import annotations

import bisect
import collections
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import trace  # noqa: E402

MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = (trace.SPAN_PREFIX, "repro.")
OTHER_MODULE = "other_module"
UNSCOPED = "unscoped"
SHORT_GAP_NS = 12_000_000     # in-loop gaps: under 12 ms
#: the per-flow CC block: every phase scope of the step but the link
#: reductions and the trace decimation, which have metrics of their own
FLOW_BLOCK = ("fluid.select", "fluid.generate", "fluid.transfer", "fluid.pfc",
              "fluid.mark", "fluid.notify", "fluid.react")


def load_events(path: str) -> dict:
    """``{"device": {plane: [(op, start_ns, end_ns, module)]},
    "host": [(span, start_ns, end_ns)]}``: each op with the module whose
    ``XLA Modules`` event holds its start, host spans named ``bench.*``
    or ``repro.*``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(trace.DEVICE_PREFIX) and name[len(trace.DEVICE_PREFIX):].isdigit():
            ops, mods = [], []
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events]
                if line.name == trace.OPS_LINE:
                    ops += evs
                elif line.name == MODULES_LINE:
                    mods += [(s, e, n.split("(")[0]) for n, s, e in evs]
            device[name] = with_modules(ops, mods)
        elif name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events if ev.name.startswith(SPAN_PREFIXES)]
    return {"device": device, "host": host}


def with_modules(ops, mods) -> list:
    """Each op of ``ops`` as ``(name, s, e, module)``; the module is the
    one of ``mods`` ``(s, e, module)`` whose interval holds the op's
    start, else ``""``."""
    mods = sorted(mods)
    starts = [s for s, _, _ in mods]
    out = []
    for n, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        out.append((n, s, e, mods[i][2] if i >= 0 and s < mods[i][1] else ""))
    return out


def op_scope(name: str, module: str, scopes: dict) -> str:
    """The scope of a trace op (``%fusion.12 = ...``) of ``module``."""
    table = scopes.get(module)
    if table is None:
        return OTHER_MODULE
    return table.get(name.partition(" ")[0].lstrip("%"), UNSCOPED)


def _label(spans, a, b) -> str:
    mid = (a + b) / 2
    open_ = [(e - s, n) for n, s, e in spans if s <= mid < e]
    return min(open_)[1] if open_ else trace.SPAN_PREFIX + "window"


def reduce_scoped(events: dict, scopes: dict, n_chips: int = 1, top: int = 10) -> dict:
    """``bench.trace.reduce`` of ``events`` plus the scoped keys (module
    docstring).  ``scopes``: ``{module: {instruction: scope}}``."""
    plain = {"device": {p: [op[:3] for op in ops] for p, ops in events["device"].items()},
             "host": [h for h in events["host"] if h[0].startswith(trace.SPAN_PREFIX)]}
    red = trace.reduce(plain, n_chips=n_chips, top=top)
    windows = [(s, e) for n, s, e in plain["host"] if n == trace.SPAN_PREFIX + "traced"] or \
        [(s, e) for n, s, e in plain["host"] if n == trace.SPAN_PREFIX + "window"]
    lo, hi = windows[0]
    planes = sorted(events["device"], key=lambda p: int(p[len(trace.DEVICE_PREFIX):]))[:n_chips]

    scope_ns, scoped_ops = collections.Counter(), collections.Counter()
    for p in planes:
        ops = [op for op in events["device"][p] if op[2] > lo and op[1] < hi]
        scope_of = {}
        for n, _, _, m in ops:
            scope_of.setdefault((n, m), op_scope(n, m, scopes))
        keyed = [((n, m), s, e) for n, s, e, m in ops]
        for key, ns in trace.self_times(keyed, lo, hi).items():
            scope_ns[scope_of[key]] += ns
            scoped_ops[f"{trace.short_name(key[0])} [{scope_of[key]}]"] += ns

    # gaps on the first chip, each with the op that ends where it starts
    ops0 = sorted((max(s, lo), min(e, hi), n, m) for n, s, e, m in
                  events["device"][planes[0]] if e > lo and s < hi)
    gaps, prev, last = [], lo, None
    for s, e, n, m in ops0 + [(hi, hi, None, None)]:
        if s > prev:
            gaps.append((prev, s, last))
        if e > prev:
            prev, last = e, (n, m)
    spans = [(n, s, e) for n, s, e in events["host"]
             if n not in (trace.SPAN_PREFIX + "window", trace.SPAN_PREFIX + "traced")]
    by_scope, by_span, long_gaps = collections.Counter(), collections.Counter(), []
    for a, b, before in gaps:
        if b - a < SHORT_GAP_NS:
            by_scope[op_scope(before[0], before[1], scopes) if before else
                     trace.SPAN_PREFIX + "window"] += b - a
        if b - a >= trace.OP_GAP_NS:
            label = _label(spans, a, b)
            by_span[label] += b - a
            long_gaps.append((b - a, label))
    long_gaps.sort(reverse=True)

    span_s = collections.defaultdict(lambda: [0, 0.0])
    for n, s, e in events["host"]:
        if n.startswith("repro.") and lo <= s and e <= hi:
            span_s[n][0] += 1
            span_s[n][1] += (e - s) / 1e9
    k = len(planes)
    return dict(
        red,
        scope_s={sc: ns / k / 1e9 for sc, ns in scope_ns.most_common()},
        span_s=dict(span_s),
        op_gaps_by_scope={sc: ns / 1e9 for sc, ns in by_scope.most_common()},
        idle_by_span={sp: ns / 1e9 for sp, ns in by_span.most_common()},
        idle_gaps_by_span=[[label, ns / 1e9] for ns, label in long_gaps[:top]],
        device_ops_scoped=[[n, ns / k / 1e9] for n, ns in scoped_ops.most_common(top)],
        unscoped_ops=[[n, ns / k / 1e9] for n, ns in scoped_ops.most_common()
                      if n.endswith(f"[{UNSCOPED}]")][:top])


def per_step(red: dict, steps: int) -> dict:
    """Device microseconds per simulated step of each part of the step,
    from a scoped reduction of ``steps`` traced steps."""
    sc = red["scope_s"]

    def us(*names):
        return sum(sc.get(n, 0.0) for n in names) / steps * 1e6

    return {"reduce_us": us("fluid.reduce"), "flow_block_us": us(*FLOW_BLOCK),
            "decimate_us": us("fluid.decimate"), "unscoped_us": us(UNSCOPED),
            "other_module_us": us(OTHER_MODULE), "step_device_us": red["busy_s"] / steps * 1e6}


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="", help="keep the trace here (default: under "
                    ".bench_cache/out of the checkout)")
    args = ap.parse_args(argv)

    from bench import env, harness, traffic
    from bench.run import run_cell
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    config, mix = harness.load_config(cell["config"]), traffic.load(cell["traffic"])
    devs = env.devices(int(cell["chips"]))
    peaks = env.peaks(devs[0].device_kind)
    env.use_compile_cache()
    out = args.out or os.path.join(env.OUT_DIR, "scoped.xplane.pb")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    line, notes = run_cell(bench, cell, config, mix, args.seed, args.seconds, True,
                           devs, peaks, keep_trace_to=out)
    try:
        from repro.core import obs
    except ImportError:        # a program without spans and scopes
        scopes = {}
    else:
        scopes = obs.sweep_op_scopes()
    red = reduce_scoped(load_events(out), scopes, n_chips=len(devs))
    steps = int(re.search(r"steps_traced=(\d+)", " ".join(notes)).group(1))
    print(json.dumps({"cell": cell["name"], "seed": args.seed, "line": line,
                      "per_step": per_step(red, steps), "steps_traced": steps,
                      "scoped": {k: red[k] for k in (
                          "scope_s", "span_s", "op_gaps_by_scope", "idle_by_span",
                          "idle_gaps_by_span", "device_ops_scoped", "unscoped_ops",
                          "busy_s", "window_s",
                          "op_gap_s", "n_op_gaps")},
                      "notes": notes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
