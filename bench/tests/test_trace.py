"""The trace reduction, on a small trace recorded on a TPU v5e chip
(a tiny sweep of the paper's CLOS under ``bench/readings.py trace``)."""

import gzip
import os
import shutil

import numpy as np
import pytest

from bench import trace
from conftest import DATA

XPLANE = os.path.join(DATA, "tiny.xplane.pb.gz")


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(XPLANE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.load_events(str(path))


def brute_busy(ops, lo, hi):
    """Busy nanoseconds by marking a boolean timeline, 1 ns resolution
    relative to the window."""
    line = np.zeros(int(hi - lo), bool)
    for _, s, e in ops:
        a, b = max(int(s - lo), 0), min(int(e - lo), len(line))
        if b > a:
            line[a:b] = True
    return int(line.sum())


def test_trace_has_device_and_spans(events):
    assert events["device"] and any(events["device"].values())
    names = {n for n, _, _ in events["host"]}
    assert {"bench.window", "bench.launch"} <= names


def test_busy_is_the_union_of_op_intervals(events):
    red = trace.reduce(events)
    (lo, hi), = [(s, e) for n, s, e in events["host"] if n == "bench.window"]
    plane = sorted(events["device"], key=lambda p: int(p.rsplit(":", 1)[1]))[0]
    busy = brute_busy(events["device"][plane], lo, hi)
    assert red["busy_s"] == pytest.approx(busy / 1e9, rel=1e-6, abs=2e-9)
    assert 0 < red["busy_s"] < red["window_s"] == pytest.approx((hi - lo) / 1e9)


def test_gaps_and_ops(events):
    red = trace.reduce(events, top=10**6)
    gaps = sum(s for _, s in red["idle_gaps"])
    assert gaps + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-9, abs=1e-9)
    assert all(label.startswith("bench.") for label, _ in red["idle_gaps"])
    secs = [s for _, s in red["device_ops"]]
    assert secs == sorted(secs, reverse=True) and sum(secs) >= red["busy_s"] * (1 - 1e-9)
    short = trace.reduce(events)
    assert len(short["device_ops"]) <= 10 and len(short["idle_gaps"]) <= 10
    assert 0 <= short["op_gap_s"] <= gaps + 1e-9
