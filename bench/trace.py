"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

* device busy time: the union of the intervals of the operations on each
  device's "XLA Ops" line, averaged over the chips used;
* the traced window: the benchmark's ``bench.traced`` host span (or, in
  a trace without it, ``bench.window``);
* the device operations that took most time, by the names the trace
  gives them, each by its self time (the time no operation nested inside
  it covers: a loop's body ops are its children), averaged over the chips;
* the idle gaps inside the window (no operation on the first chip), each
  labelled with the innermost ``bench.*`` host span open at its middle,
  and the idle time in gaps under 1 ms (``op_gap_s``), which lie between
  the device operations of one program rather than between programs.

Host and device events of one profile share one clock: the profiler puts
device timestamps on the host's.
"""

from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
OP_GAP_NS = 1_000_000     # idle gaps under 1 ms: between device ops of a program


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_events(path: str) -> dict:
    """{"device": {plane: [(name, start_ns, end_ns)]}, "host": [...]}"""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and plane.name[len(DEVICE_PREFIX):].isdigit():
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events]
            device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events if ev.name.startswith(SPAN_PREFIX)]
    return {"device": device, "host": host}


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[3,4288]{...} fusion(...)`` -> ``%fusion.12 f32[3,4288] fusion``."""
    head, _, rest = hlo.partition(" = ")
    if not rest:
        return head
    if rest.startswith("("):
        return f"{head} tuple {rest[rest.find(')') + 1:].split('(')[0].strip()}"
    ty, _, op = rest.partition(" ")
    return f"{head} {re.sub(r'[{][^}]*[}]', '', ty)} {op.split('(')[0]}"


def self_times(ops, lo, hi) -> collections.Counter:
    """Nanoseconds each op ran in [lo, hi) that no op nested inside it
    covers (a loop op holds its body's ops)."""
    spans = sorted(((max(s, lo), min(e, hi), n) for n, s, e in ops if e > lo and s < hi),
                   key=lambda x: (x[0], -x[1]))
    out, stack = collections.Counter(), []
    for s, e, n in spans:
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= min(e, stack[-1][0]) - s
        out[n] += e - s
        stack.append((e, n))
    return out


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def reduce(events: dict, n_chips: int = 1, top: int = 10) -> dict:
    """busy_s, window_s, device_ops [[name, s]], idle_gaps [[label, s]],
    with n_ops, op_counts (the three most frequent ops) and op_gap_s."""
    windows = [(s, e) for n, s, e in events["host"] if n == SPAN_PREFIX + "traced"] or \
        [(s, e) for n, s, e in events["host"] if n == SPAN_PREFIX + "window"]
    if not windows:
        raise ValueError("the trace holds no bench.traced or bench.window span")
    lo, hi = windows[0]
    planes = sorted(events["device"], key=lambda p: int(p[len(DEVICE_PREFIX):]))
    planes = planes[:n_chips]
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy, per_op, counts = [], collections.Counter(), collections.Counter()
    merged0 = None
    for p in planes:
        ops = [(short_name(n), s, e) for n, s, e in events["device"][p]
               if e > lo and s < hi]
        merged = clip(union((s, e) for _, s, e in ops), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        per_op.update(self_times(ops, lo, hi))
        counts.update(n for n, _, _ in ops)
        if merged0 is None:
            merged0 = merged
    gaps, prev = [], lo
    for s, e in merged0 + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    spans = [(n, s, e) for n, s, e in events["host"]
             if n not in (SPAN_PREFIX + "window", SPAN_PREFIX + "traced")]

    def label(a, b):
        mid = (a + b) / 2
        open_ = [(e - s, n) for n, s, e in spans if s <= mid < e]
        return min(open_)[1] if open_ else "bench.window"

    short = [b - a for a, b in gaps if b - a < OP_GAP_NS]
    gaps.sort(key=lambda g: g[0] - g[1])
    return dict(
        busy_s=sum(busy) / len(busy) / 1e9,
        window_s=(hi - lo) / 1e9,
        device_ops=[[n, ns / len(planes) / 1e9] for n, ns in per_op.most_common(top)],
        idle_gaps=[[label(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
        n_ops=sum(len(events["device"][p]) for p in planes),
        op_counts=[[n, c] for n, c in counts.most_common(3)],
        op_gap_s=sum(short) / 1e9, n_op_gaps=len(short))
