"""The scoped trace reduction: device time by the program's phase
scopes, idle time by its host spans, on a synthetic trace and on the
small trace recorded on a TPU v5e chip."""

import gzip
import os
import shutil

import pytest

from bench import scopes, trace
from conftest import DATA

MS = 1_000_000
SCAN = "jit_scan_fn"
SCOPES = {SCAN: {"fusion.1": "fluid.reduce", "fusion.2": "fluid.react",
                 "copy.1": "fluid.decimate", "while.9": "unscoped"}}


def synthetic() -> dict:
    """One launch: 2 ms of staging (an eager ``%copy.1`` of another
    module), a 2 ms gap, then the scan module: a loop holding three body
    ops, a 1 ms gap, one more op, and a 3 ms gap while the host
    fetches."""
    ops = [
        ("%copy.1 = f32[4] copy(...)", 0, 2 * MS, "jit_concatenate"),
        ("%while.9 = (f32[4]) while(...)", 4 * MS, 10 * MS, SCAN),
        ("%fusion.1 = f32[4] fusion(...)", 4 * MS, 6 * MS, SCAN),
        ("%fusion.2 = f32[4] fusion(...)", 6 * MS, 7 * MS, SCAN),
        ("%copy.1 = f32[4] copy(...)", 8 * MS, 9 * MS, SCAN),
        ("%fusion.1 = f32[4] fusion(...)", 11 * MS, 12 * MS, SCAN),
    ]
    host = [("bench.window", 0, 20 * MS), ("bench.traced", 0, 15 * MS),
            ("bench.launch", 0, 15 * MS),
            ("repro.sweep.stage", 0, int(3.5 * MS)),
            ("repro.sweep.execute", int(3.5 * MS), 12 * MS),
            ("repro.sweep.fetch", 12 * MS, 15 * MS),
            ("repro.sweep.fetch", 16 * MS, 17 * MS)]     # after the traced window
    return {"device": {"/device:TPU:0": ops}, "host": host}


def test_scope_self_times_and_module_filtering():
    red = scopes.reduce_scoped(synthetic(), SCOPES)
    s = red["scope_s"]
    assert s["fluid.reduce"] == pytest.approx(3e-3)       # 2 ms in the loop + 1 ms after
    assert s["fluid.react"] == pytest.approx(1e-3)
    # the scan's %copy.1 is decimation; staging's %copy.1 is another module's
    assert s["fluid.decimate"] == pytest.approx(1e-3)
    assert s["other_module"] == pytest.approx(2e-3)
    assert s["unscoped"] == pytest.approx(2e-3)           # the loop's own 7-8 and 9-10 ms
    assert sum(s.values()) == pytest.approx(red["busy_s"])
    assert red["device_ops_scoped"][0] == ["%fusion.1 f32[4] fusion [fluid.reduce]",
                                           pytest.approx(3e-3)]
    # without the map every op is another module's
    assert set(scopes.reduce_scoped(synthetic(), {})["scope_s"]) == {"other_module"}


def test_gaps_by_scope_and_by_span():
    red = scopes.reduce_scoped(synthetic(), SCOPES)
    # gaps: 2-4 ms after staging's copy, 10-11 ms after the loop, 12-15 ms
    assert red["op_gaps_by_scope"] == {"other_module": pytest.approx(2e-3),
                                       "unscoped": pytest.approx(1e-3),
                                       "fluid.reduce": pytest.approx(3e-3)}
    assert red["idle_by_span"] == {"repro.sweep.fetch": pytest.approx(3e-3),
                                   "repro.sweep.stage": pytest.approx(2e-3),
                                   "repro.sweep.execute": pytest.approx(1e-3)}
    assert [g[0] for g in red["idle_gaps_by_span"]] == [
        "repro.sweep.fetch", "repro.sweep.stage", "repro.sweep.execute"]
    assert red["span_s"] == {"repro.sweep.stage": [1, pytest.approx(3.5e-3)],
                             "repro.sweep.execute": [1, pytest.approx(8.5e-3)],
                             "repro.sweep.fetch": [1, pytest.approx(3e-3)]}
    # the old reduction labels with bench.* spans only
    assert {g[0] for g in red["idle_gaps"]} == {"bench.launch"}


def test_per_step_splits_the_step():
    red = scopes.reduce_scoped(synthetic(), SCOPES)
    ps = scopes.per_step(red, steps=10)
    assert ps["reduce_us"] == pytest.approx(300.0)
    assert ps["flow_block_us"] == pytest.approx(100.0)
    assert ps["decimate_us"] == pytest.approx(100.0)
    parts = sum(ps[k] for k in ("reduce_us", "flow_block_us", "decimate_us",
                                "unscoped_us", "other_module_us"))
    assert parts == pytest.approx(ps["step_device_us"])


def test_with_modules_takes_the_enclosing_module():
    ops = [("a", 5, 6), ("b", 15, 16), ("c", 25, 26)]
    mods = [(10, 20, "m2"), (0, 10, "m1")]
    assert [m for *_, m in scopes.with_modules(ops, mods)] == ["m1", "m2", ""]


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(os.path.join(DATA, "tiny.xplane.pb.gz"), "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_old_keys_read_the_same_on_a_chip_trace(xplane):
    old = trace.reduce(trace.load_events(xplane))
    ev = scopes.load_events(xplane)
    new = scopes.reduce_scoped(ev, {})
    assert {k: new[k] for k in old} == old
    mods = {m for ops in ev["device"].values() for *_, m in ops}
    assert "jit_scan_fn" in mods
    # every busy second of the window is some module's op, and the
    # sweep's own module holds most of them
    by_module = scopes.reduce_scoped(ev, {"jit_scan_fn": {}})["scope_s"]
    assert sum(by_module.values()) >= new["busy_s"] * (1 - 1e-9)
    assert by_module["unscoped"] > by_module["other_module"]
