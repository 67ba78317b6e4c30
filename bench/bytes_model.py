"""Bytes one fluid step moves, as the benchmark counts them.

A copy of the program's ``repro.fleet.plan.fluid_step_bytes`` as it
stands when the benchmark was defined (``bench/tests/test_bytes_model.py``
checks that the two still agree).  It depends only on shapes, not on which
engine runs the step, so it stays the yardstick when the program changes
its own copy.
"""

from __future__ import annotations


def fluid_step_bytes(n_flows: int, n_paths: int, n_hops: int,
                     n_links: int, n_vcs: int = 1) -> float:
    """Analytic HBM bytes of one fluid step of one run (f32 vectors): the
    link reductions make 3 passes with (3, 3, 2) channels over F*K*H
    incidence rows into L*V (+1 pad) link sums, and the per-flow block
    makes one round trip of its ~40 [F] state vectors."""
    n = n_flows * n_paths * n_hops
    red = sum(c * n * 4 + n * 4 + c * (n_links * n_vcs + 1) * 4
              for c in (3, 3, 2))
    flow = 40 * n_flows * 4
    return float(red + flow)
