"""Beyond-paper: DC-scale CC stepping + sweep throughput.

The paper's scenario has 5 flows; a datacenter NIC fleet runs the RP/ERP
machine for 10^5+ flows.  This measures flow-updates/second of the
reaction-point update at increasing F (the jnp reference path), the
full fluid-model step at permutation-traffic scale, and the batched
Sweep engine's run-throughput (an incast-degree x scheme grid as one
launch vs the legacy one-run-at-a-time loop).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (CCConfig, CCScheme, ScenarioSpec, Sweep,
                        random_permutation, run)
from repro.kernels import ref


def bench_rp_updates(F: int, iters: int = 50) -> float:
    r = np.random.RandomState(0)
    p = ref.RPParams(g=1 / 256, rate_decrease=0.5, timer_T=55e-6,
                     byte_B=10e6, rai=5e6, rhai=25e6, fr_stages=5,
                     min_rate=1e6, line_rate=12.5e9, dt=1e-6)
    st = ref.RPState(*[jnp.asarray(r.rand(F), jnp.float32)
                       for _ in range(8)])
    cnp = jnp.asarray(r.rand(F) > 0.7)

    @jax.jit
    def step(s):
        return ref.rp_update_ref(s, cnp, p)

    st = step(st)
    jax.block_until_ready(st)
    t0 = time.perf_counter()
    for _ in range(iters):
        st = step(st)
    jax.block_until_ready(st)
    dt = (time.perf_counter() - t0) / iters
    return F / dt          # flow-updates per second


def bench_fluid_step(n_flows: int, n_steps: int = 2000) -> float:
    cfg = CCConfig(scheme=CCScheme.DCQCN_REV)
    scn = random_permutation(cfg, n_flows=n_flows, arity=4)
    t0 = time.perf_counter()
    run(scn, cfg, n_steps=n_steps)
    dt = time.perf_counter() - t0
    return n_steps / dt    # sim steps / wall second (incl. jit)


def bench_sweep(n_steps: int = 2000) -> tuple[float, float, int]:
    """Scheme x incast-degree grid: one launch vs a python run() loop.

    Returns (sweep_s, loop_s, n_points)."""
    cfg = CCConfig()
    degrees = (2, 4, 8, 16)
    sweep = Sweep.grid(
        configs={s.name: cfg.replace(scheme=s) for s in CCScheme},
        scenarios={f"incast{n}": ScenarioSpec.incast(n) for n in degrees})
    t0 = time.perf_counter()
    sweep.run(n_steps=n_steps)
    sweep_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for p in sweep.points:
        run(p.scenario, p.cfg, n_steps=n_steps)
    loop_s = time.perf_counter() - t0
    return sweep_s, loop_s, len(sweep.points)


def main() -> list[tuple]:
    out = []
    for F in (1_000, 10_000, 100_000):
        ups = bench_rp_updates(F)
        out.append((f"cc_scale.rp_updates.F{F}", 1e6 / (ups / F),
                    f"{ups:.3g} flow-updates/s"))
    for nf in (16, 64):
        sps = bench_fluid_step(nf)
        out.append((f"cc_scale.fluid_step.flows{nf}", 1e6 / sps,
                    f"{sps:.1f} sim-steps/s"))
    sweep_s, loop_s, n = bench_sweep()
    out.append((f"cc_scale.sweep.points{n}", sweep_s / n * 1e6,
                f"one-launch {sweep_s:.2f}s vs run-loop {loop_s:.2f}s "
                f"({loop_s / max(sweep_s, 1e-9):.1f}x)"))
    return out


if __name__ == "__main__":
    for row in main():
        print(",".join(str(x) for x in row))
