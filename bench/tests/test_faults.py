"""Each fault a cell can have makes ``correct`` come out false.

The harness runs here without its look for a chip (CPU devices, tiny
configurations), and the timed path is broken underneath it: a step that
returns its state unchanged, half of every batch left out, an answer
altered where it is produced.  The cells run on one chip, so there is no
exchange between chips to leave out.  Every cell is a sweep, so one tiny
sweep stands for them."""

import json
import os

import jax
import numpy as np
import pytest

from bench import harness, traffic
from bench import run as brun
from conftest import DATA, ROOT

CELL = "ft1000.a2a_storm.sweep"


@pytest.fixture
def fresh_cache():
    from repro.core import SWEEP_EXEC_CACHE
    SWEEP_EXEC_CACHE.clear()
    yield
    SWEEP_EXEC_CACHE.clear()


def run_tiny(seed=5):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    cfg = harness.load_config("ft64", DATA)
    line, _ = brun.run_cell(bench, cell, cfg, traffic.load("a2a_storm_tiny", DATA), seed,
                            2.0, False, jax.devices()[:1], {"hbm_bytes_per_s": 1.0})
    return line


def test_sound_run_is_correct(fresh_cache):
    line = run_tiny()
    assert line["correct"] is True
    assert set(line["compared"]) == set(harness.load_limits(CELL))


def test_state_left_unchanged(fresh_cache, monkeypatch):
    import repro.core.experiments as ex
    real = ex.fluid_step

    def frozen(st, *a, **kw):
        return st, real(st, *a, **kw)[1]

    monkeypatch.setattr(ex, "fluid_step", frozen)
    assert run_tiny()["correct"] is False


def test_half_of_batch_left_out(fresh_cache, monkeypatch):
    from repro.core import Sweep
    real = Sweep.run

    def half(self, *a, **kw):
        res = real(self, *a, **kw)
        R = len(res.points)
        keep = (R + 1) // 2

        def cut(x):
            x = np.array(x)
            x[keep:] = x[:R - keep]
            return x

        res.traces = jax.tree.map(cut, res.traces)
        res.final = jax.tree.map(cut, res.final)
        return res

    monkeypatch.setattr(Sweep, "run", half)
    assert run_tiny()["correct"] is False


@pytest.mark.parametrize("part", ["trace", "final"])
def test_answer_altered(part, fresh_cache, monkeypatch):
    """One flow's answer altered where the launch produces it: its traced
    delivered bytes at the last sample 25% high, or its final bytes
    queued at the first hop doubled."""
    from repro.core import Sweep
    real = Sweep.run

    def altered(self, *a, **kw):
        res = real(self, *a, **kw)
        if part == "trace":
            d = np.array(res.traces.delivered)
            d[0, -1, np.argmax(d[0, -1])] *= 1.25
            res.traces = res.traces._replace(delivered=d)
        else:
            q = np.array(res.final.qh)
            f = np.argmax(q[0, :, 0])
            q[0, f, 0] = 2 * q[0, f, 0] + 1e5
            res.final = res.final._replace(qh=q)
        return res

    monkeypatch.setattr(Sweep, "run", altered)
    assert run_tiny()["correct"] is False
