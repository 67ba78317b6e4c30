"""Double-buffered async device→host trace streaming.

``Sweep.run`` keeps the whole decimated trace device-resident until the
scan returns — at pod scale that is the memory ceiling, and a preempted
run loses everything.  ``stream_sweep`` runs the SAME staged batch one
trace window at a time (the executable is the sweep scan with the outer
scan depth pinned to 1 — every other static knob, and therefore the
whole numeric body, is identical), handing each window's device arrays
to a background spiller thread that ``jax.device_get``\\ s them into
per-field ``.npy`` spill files while the device advances the next
window.  The bounded hand-off queue (``buffer_windows`` deep, default
2) is the double buffer: at most that many windows are ever in flight,
so host memory stays O(window), not O(trace).

Reassembly transposes the spill ([T, R, ...]) into the [R, T, ...]
layout of ``SweepResult`` exactly like ``Sweep.run`` does — the result
is **bitwise identical** to the in-memory launch (asserted over every
trace field and the final state in ``tests/test_fleet.py``).
"""

from __future__ import annotations

import os
import queue
import shutil
import tempfile
import threading

import jax
import numpy as np

from repro.core.experiments import (Sweep, SweepResult, _sweep_executable)
from repro.core.simulator import TraceSample


class _Spill:
    """Per-field [T, ...] spill files under one directory."""

    def __init__(self, directory: str, n_samples: int):
        self.directory = directory
        self.n_samples = n_samples
        self._mm: dict[str, np.memmap] = {}
        os.makedirs(directory, exist_ok=True)

    def write(self, t: int, window: dict) -> None:
        for f, v in window.items():
            if v is None:
                continue
            mm = self._mm.get(f)
            if mm is None:
                mm = np.lib.format.open_memmap(
                    os.path.join(self.directory, f"{f}.npy"), mode="w+",
                    dtype=v.dtype, shape=(self.n_samples,) + v.shape[1:])
                self._mm[f] = mm
            mm[t] = v[0]              # the window's single sample row

    def arrays(self) -> dict[str, np.ndarray]:
        for mm in self._mm.values():
            mm.flush()
        return {f: np.asarray(mm) for f, mm in self._mm.items()}


def stream_sweep(sweep: Sweep, n_steps: int | None = None,
                 trace_every: int | None = None, *,
                 spill_dir: str | None = None,
                 buffer_windows: int = 2,
                 reduce: str = "fused",
                 pad_runs_to: int | None = None,
                 min_delay_slots: int | None = None,
                 dense_rows: int | None = None,
                 temperature: float = 0.0,
                 min_switches: int | None = None) -> SweepResult:
    """``Sweep.run`` with per-window device→host trace streaming.

    Accepts ``Sweep.run``'s knobs (minus ``mesh`` — stream one host's
    shard; the fleet scheduler is the multi-host axis).  ``spill_dir``
    keeps the raw window spill on disk (the fleet journal points
    there); ``None`` spills to a temp dir deleted after reassembly.
    ``buffer_windows`` bounds the windows in flight (the double
    buffer); the producer blocks when the spiller falls behind, so
    streaming can throttle but never drop or reorder a window.
    """
    if buffer_windows < 1:
        raise ValueError(f"buffer_windows must be >= 1: {buffer_windows}")
    static, args, n_samples, k = sweep._prepare(
        n_steps, trace_every, mesh=None, reduce=reduce,
        pad_runs_to=pad_runs_to, min_delay_slots=min_delay_slots,
        dense_rows=dense_rows, temperature=temperature,
        min_switches=min_switches)
    st, sd_b, par_b = args
    # the window program: the same scan, outer depth 1.  Everything
    # numeric (inner substep scan, reduction engine) is
    # bit-identical to the full-depth program; only the trace stacking
    # depth changes, so T windows chain to the full run exactly.
    exec_fn = _sweep_executable((1,) + static[1:], args)

    tmp = tempfile.mkdtemp(prefix="sweep_spill_") if spill_dir is None \
        else spill_dir
    spill = _Spill(tmp, n_samples)
    q: "queue.Queue" = queue.Queue(maxsize=buffer_windows)
    err: list[BaseException] = []

    def spiller():
        while True:
            item = q.get()
            if item is None:
                return
            t, window = item
            try:
                host = jax.device_get(window)
                spill.write(t, dict(zip(TraceSample._fields, host)))
            except BaseException as e:     # surfaced after the loop
                err.append(e)
                return

    def put(item) -> bool:
        """Bounded put that bails out if the spiller died (a dead
        consumer must never deadlock the producer on a full queue)."""
        while not err:
            try:
                q.put(item, timeout=0.1)   # blocks at the buffer bound
                return True
            except queue.Full:
                continue
        return False

    th = threading.Thread(target=spiller, name="trace-spiller",
                          daemon=True)
    th.start()
    try:
        for t in range(n_samples):
            st, tr = exec_fn(st, sd_b, par_b)
            if not put((t, tuple(tr))):
                break                      # spiller died: stop producing
    finally:
        put(None)
        th.join()
    if err:
        if spill_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
        raise err[0]

    R = len(sweep.points)
    arrays = spill.arrays()
    traces = TraceSample(**{
        f: (np.moveaxis(arrays[f], 0, 1)[:R] if f in arrays else None)
        for f in TraceSample._fields})
    final = jax.tree.map(lambda x: np.asarray(x)[:R], jax.device_get(st))
    dt = sweep.points[0].cfg.sim.dt
    times = (np.arange(n_samples) + 1) * k * dt
    if spill_dir is None:
        shutil.rmtree(tmp, ignore_errors=True)
    return SweepResult(points=sweep.points, times=times, traces=traces,
                       final=final, trace_every=k)
