"""Invariant / property harness over the CC-stage registry product.

Every (marking x notification x reaction) combo registered in
``repro.core.cc`` — 36 with the built-ins — must satisfy the fluid
model's physical invariants on randomized fabrics and workloads, at
one VC and at several:

  * byte conservation — every offered byte is delivered, waiting in a
    NIC backlog, or queued in the fabric (f32 accumulation tolerance);
  * queue sanity — no negative queues, and the hottest port stays
    within the per-port buffer (PFC's whole job);
  * PFC hysteresis legality — a queue's pause rises only at XOFF and
    re-enables only below XON (checked step-by-step against a host
    mirror of the per-(wire, VC) backlog reduction);
  * reaction rate clamps — flow rates stay in (0, line_rate].

Each sampled point runs the full 36-combo product as ONE Sweep launch
(the stage registry is traced data), so the harness scales by
scenarios, not by configs.
"""

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import CCSpec, Sweep, cc
from repro.core.fluid import init_state, make_step_fn
from repro.core.params import LinkParams
from repro.core.workloads import (group_shift, hol_victim_incast, hotspot,
                                  incast_storm)
from repro.net import FabricSpec

N_STEPS = 300


def _stage_product() -> list:
    return [(m, n, r) for m in cc.MARKING.names()
            for n in cc.NOTIFICATION.names()
            for r in cc.REACTION.names()]


def test_stage_product_covers_the_advertised_grid():
    """The built-in registries multiply out to (at least) the 36 combos
    this harness claims to cover; shrinkage means a stage went missing."""
    assert len(_stage_product()) >= 36


# ---------------------------------------------------------------------------
# property sweep: invariants across the full stage product
# ---------------------------------------------------------------------------

def _fabric(kind: str) -> FabricSpec:
    return (FabricSpec.dragonfly(a=2, p=2, h=2) if kind == "dfly"
            else FabricSpec.fat_tree(4, taper=2))


def _workload(kind: str, seed: int, n_nodes: int):
    t0, t1 = 0.05e-3, 2e-3
    if kind == "gshift":
        return group_shift(n_nodes // 4, 4, t_start=t0, t_stop=t1)
    if kind == "storm":
        return incast_storm(min(8, n_nodes - 2), 2, n_nodes, seed=seed,
                            t_start=t0, t_stop=t1)
    return hotspot(8, n_nodes, seed=seed, t_start=t0, t_stop=t1)


#: (fabric, workload, seed, n_vcs) — the fallback runs all of these;
#: hypothesis additionally shuffles which it visits per run.
SAMPLES = [
    ("dfly", "gshift", 0, 1),
    ("ft", "storm", 1, 1),
    ("ft", "hot", 2, 2),
    ("dfly", "storm", 0, 2),
    ("ft", "storm", 3, 2),
    ("dfly", "hot", 1, 1),
]


def _check_point(name: str, res, cfg) -> None:
    f = res.final
    offered = np.asarray(f.offered)
    acct = (np.asarray(f.delivered) + np.asarray(f.nicq)
            + np.asarray(f.qh).sum(1))
    np.testing.assert_allclose(acct, offered, rtol=1e-4, atol=1e3,
                               err_msg=f"{name}: bytes not conserved")
    assert np.asarray(f.qh).min() >= -1e-3, name
    assert np.asarray(f.nicq).min() >= -1e-3, name
    # PFC keeps the hottest port inside its buffer (xoff sits at 75%
    # with headroom for one step of in-flight arrivals)
    assert res.max_q.max() <= cfg.link.port_buffer, \
        (name, float(res.max_q.max()))
    # reaction rate clamps: positive, never above line rate
    rate = np.asarray(res.rate)
    assert rate.min() > 0.0, name
    assert rate.max() <= cfg.link.line_rate * (1 + 1e-5), \
        (name, float(rate.max()))
    assert np.isfinite(np.asarray(f.rate)).all(), name


@settings(max_examples=6, deadline=None)
@given(sample=st.sampled_from(SAMPLES))
def test_invariants_hold_across_stage_product(sample):
    fab_kind, wl_kind, seed, n_vcs = sample
    fab = _fabric(fab_kind)
    spec = _workload(wl_kind, seed, fab.n_nodes).spec(
        fabric=fab, label=f"{fab_kind}/{wl_kind}/{seed}")
    link = LinkParams(n_vcs=n_vcs)
    configs = {f"{m}+{n}+{r}": CCSpec(marking=m, notification=n,
                                      reaction=r, link=link)
               for m, n, r in _stage_product()}
    res = Sweep.grid(configs=configs, scenarios={"wl": spec}).run(
        n_steps=N_STEPS)
    assert len(res.names) == len(configs)
    for name in res.names:
        _check_point(f"{sample}/{name}", res[name],
                     configs[name.rsplit("/", 1)[0]])


# ---------------------------------------------------------------------------
# PFC hysteresis legality: step-level check against a host-side mirror
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_vcs", [1, 2])
def test_pfc_hysteresis_legality(n_vcs):
    """Pause transitions obey the hysteresis band, per (wire, VC) queue.

    Replays the scan host-side: after every step, the per-queue backlog
    B is recomputed from ``qh`` exactly as phase 3 does (sum over
    non-final hops into ``route * n_vcs + vc``), and each pause
    transition is checked — a rise demands B at/above the queue's XOFF
    threshold, a fall demands B at/below XON (small f32 reduction-order
    epsilon).  The shared-pool escape hatch is excluded by construction:
    the scenario's total queued bytes stay far under ``pool_xoff``.
    """
    cfg = CCSpec(marking="cp", notification="np", reaction="pfc",
                 link=LinkParams(n_vcs=n_vcs))
    wl = hol_victim_incast(4, 64, t_start=0.1e-3, victim_delay=0.2e-3,
                           burst_delay=0.3e-3, t_stop=1.5e-3)
    scn = wl.spec(fabric=FabricSpec.clos3(4)).build(cfg)
    V = n_vcs
    L = scn.capacity.shape[0]
    routes = np.asarray(scn.routes)                       # [F, H]
    hops = np.asarray(scn.hops)
    vc = (np.zeros_like(routes) if scn.vc is None
          else np.asarray(scn.vc)[:, 0, :])
    F, H = routes.shape
    holds = (np.arange(H)[None, :] < (hops[:, None] - 1)) & (routes >= 0)
    qidx = np.where(holds, routes * V + vc, L * V)        # scratch at S

    xoff = cfg.link.port_buffer * cfg.link.pfc_xoff_frac / V
    xon = cfg.link.port_buffer * cfg.link.pfc_xon_frac / V
    eps = 16.0                                            # f32 sum reorder

    step = jax.jit(make_step_fn(scn, cfg))
    st = init_state(scn, cfg)
    prev_paused = np.asarray(st.paused)
    saw_rise = saw_fall = False
    for t in range(2000):   # past t_stop: drain forces pause-fall edges
        st, _ = step(st)
        paused = np.asarray(st.paused)
        assert ((paused == 0.0) | (paused == 1.0)).all(), t
        B = np.zeros(L * V + 1)
        np.add.at(B, qidx.ravel(),
                  np.where(holds, np.asarray(st.qh), 0.0).ravel())
        assert B.sum() < cfg.link.shared_buffer * cfg.link.pfc_xoff_frac
        rise = (paused > prev_paused)
        fall = (paused < prev_paused)
        assert (B[:L * V][rise] >= xoff - eps).all(), \
            (t, B[:L * V][rise].min())
        assert (B[:L * V][fall] <= xon + eps).all(), \
            (t, B[:L * V][fall].max())
        saw_rise |= bool(rise.any())
        saw_fall |= bool(fall.any())
        prev_paused = paused
    # vacuous-truth guard: the scenario must actually exercise both edges
    assert saw_rise and saw_fall


def test_pfc_hysteresis_band_is_inert():
    """A queue parked between XON and XOFF holds its pause state — the
    hysteresis, not the instantaneous level, decides (unit-level check
    of the phase-3 update rule on crafted backlogs)."""
    import jax.numpy as jnp
    xoff, xon = 384.0, 256.0
    B = jnp.asarray([300.0, 300.0, 400.0, 100.0])
    prev = jnp.asarray([1.0, 0.0, 0.0, 1.0])
    nxt = jnp.where(B > xoff, 1.0, jnp.where(B < xon, 0.0, prev))
    np.testing.assert_array_equal(np.asarray(nxt), [1.0, 0.0, 1.0, 0.0])
