"""repro.fleet: the pod-scale sweep fabric's contracts.

The acceptance bar (ISSUE 10): a fleet-executed sweep — threaded
backend, >= 3 ragged shards, async trace streaming, one induced worker
failure and one checkpoint/resume cycle — must be **bitwise identical**
to the uninterrupted single-host ``Sweep.run()`` over every trace field
and the final state, while the per-signature compile count stays at
one.  The multi-process leg runs the same plan through the
``jax.distributed`` backend in a 2-process subprocess job (pattern of
``tests/test_sharded_sweep.py``).
"""

import os
import socket
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import CCScheme, PAPER_CONFIG, ScenarioSpec, Sweep
from repro.core.experiments import SWEEP_EXEC_CACHE
from repro.core.serialize import _SIM_TRACE_FIELDS
from repro.fleet import (Abandoned, DistributedBackend, Done, FleetConfig,
                         FleetError, FleetJournal, FleetRunner,
                         PreemptedError, Retried, ThreadBackend,
                         WorkerLost, plan_sweep, run_fleet, stream_sweep)

N_STEPS, TRACE_EVERY = 400, 50


def _ragged_sweep():
    """Mixed flow counts: the planner must balance, stealers steal."""
    return Sweep.grid(
        configs={s.name: PAPER_CONFIG.replace(scheme=s)
                 for s in CCScheme},
        scenarios={"i2": ScenarioSpec.incast(2, victim=False),
                   "i6": ScenarioSpec.incast(6, victim=False),
                   "hol": ScenarioSpec.paper_incast(roll=0)})


@pytest.fixture(scope="module")
def sweep():
    return _ragged_sweep()


@pytest.fixture(scope="module")
def ref(sweep):
    return sweep.run(n_steps=N_STEPS, trace_every=TRACE_EVERY)


def assert_bitwise(res, ref):
    """Every trace field, the time base and the full final-state tree."""
    assert [p.name for p in res.points] == [p.name for p in ref.points]
    np.testing.assert_array_equal(res.times, ref.times)
    for f in _SIM_TRACE_FIELDS:
        a, b = getattr(res.traces, f), getattr(ref.traces, f)
        assert (a is None) == (b is None), f
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f)
    la = jax.tree_util.tree_flatten_with_path(res.final)[0]
    lb = jax.tree_util.tree_flatten_with_path(ref.final)[0]
    assert len(la) == len(lb)
    for (pa, ga), (_, gb) in zip(la, lb):
        assert np.array_equal(np.asarray(ga), np.asarray(gb)), \
            "final" + jax.tree_util.keystr(pa)


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------


def test_plan_deterministic_and_content_addressed(sweep):
    p1 = plan_sweep(sweep, N_STEPS, TRACE_EVERY, n_shards=4)
    p2 = plan_sweep(sweep, N_STEPS, TRACE_EVERY, n_shards=4)
    assert p1.digest == p2.digest
    assert [s.digest for s in p1.shards] == [s.digest for s in p2.shards]
    # content addressing: different work -> different digests
    p3 = plan_sweep(sweep, N_STEPS * 2, TRACE_EVERY, n_shards=4)
    assert p3.digest != p1.digest
    assert all(s3.digest != s1.digest
               for s1, s3 in zip(p1.shards, p3.shards))
    # every point covered exactly once
    seen = sorted(i for s in p1.shards for i in s.indices)
    assert seen == list(range(len(sweep.points)))


def test_plan_envelope_is_one_bucket(sweep):
    plan = plan_sweep(sweep, N_STEPS, TRACE_EVERY, n_shards=4)
    assert len(plan.buckets) == 1
    assert len(plan.shards) >= 3
    b = plan.buckets[0]
    # the envelope covers the raggedest point
    assert b.n_flows >= max(p.scenario.routes.shape[0]
                            for p in sweep.points)
    # ragged costs: LPT must not leave one shard with everything
    costs = [s.cost for s in plan.shards]
    assert max(costs) < plan.total_cost


def test_plan_fabric_bucketing(sweep):
    plan = plan_sweep(sweep, N_STEPS, TRACE_EVERY, n_shards=4,
                      bucket_by="fabric")
    assert len(plan.buckets) >= 1
    for s in plan.shards:
        b = plan.buckets[s.bucket]
        for i in s.indices:
            assert sweep.points[i].scenario.routes.shape[0] <= b.n_flows


def test_shard_sweep_and_kwargs_pin_the_envelope(sweep):
    plan = plan_sweep(sweep, N_STEPS, TRACE_EVERY, n_shards=4)
    b = plan.buckets[0]
    for s in plan.shards:
        sub = plan.shard_sweep(s)
        for p in sub.points:
            assert p.scenario.routes.shape == (b.n_flows, b.n_hops)
        kw = plan.run_kwargs(s)
        assert kw["pad_runs_to"] == b.width
        assert kw["min_switches"] == b.n_switches
        assert kw["min_delay_slots"] == b.delay_slots


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------


def test_stream_sweep_bitwise(sweep, ref):
    res = stream_sweep(sweep, n_steps=N_STEPS, trace_every=TRACE_EVERY)
    assert_bitwise(res, ref)


def test_stream_sweep_spill_dir(tmp_path, sweep, ref):
    res = stream_sweep(sweep, n_steps=N_STEPS, trace_every=TRACE_EVERY,
                       spill_dir=str(tmp_path / "spill"),
                       buffer_windows=1)
    assert_bitwise(res, ref)
    assert (tmp_path / "spill" / "delivered.npy").exists()


# ---------------------------------------------------------------------------
# the acceptance run
# ---------------------------------------------------------------------------


def test_fleet_acceptance_bitwise(tmp_path, sweep, ref):
    """Threaded backend + ragged shards + streaming + one induced
    worker failure + one preempt/resume cycle == one launch, bitwise,
    one compile per signature."""
    plan = plan_sweep(sweep, N_STEPS, TRACE_EVERY, n_shards=4)
    assert len(plan.shards) >= 3
    journal = str(tmp_path / "journal")

    killed = []

    def fault(shard, attempt, worker):
        if shard.index == 1 and not killed:
            killed.append(worker)
            raise WorkerLost(f"chaos: worker {worker} dies")

    # phase 1: worker loss + preemption after 2 commits
    with pytest.raises(PreemptedError):
        FleetRunner(plan, FleetConfig(n_workers=3, preempt_after=2),
                    journal=journal, fault_hook=fault).run()
    assert killed, "the chaos hook never fired"
    committed = len(FleetJournal(journal).completed())
    assert committed >= 2

    # phase 2: resume — journaled shards load with zero recompute
    misses0 = SWEEP_EXEC_CACHE.stats().misses
    out = FleetRunner(plan, FleetConfig(n_workers=3),
                      journal=journal).run()
    assert out.stats.resumed == committed
    assert out.stats.abandoned == 0
    # one signature bucket -> at most one compile across BOTH phases'
    # remaining shards (zero here: phase 1 already built it)
    assert SWEEP_EXEC_CACHE.stats().misses - misses0 <= 1
    assert out.stats.compiles <= 1
    assert_bitwise(out.result, ref)
    # resumed shards really came from the journal
    resumed = [o for o in out.outcomes.values()
               if isinstance(o, Done) and o.resumed]
    assert len(resumed) == committed


def test_fleet_unjournaled_run_bitwise(sweep, ref):
    out = run_fleet(sweep, N_STEPS, TRACE_EVERY,
                    config=FleetConfig(n_workers=2, n_shards=3,
                                       stream=False))
    assert_bitwise(out.result, ref)
    assert all(isinstance(o, Done) for o in out.outcomes.values())


def test_fleet_resume_zero_recompute(tmp_path, sweep, ref):
    journal = str(tmp_path / "journal")
    run_fleet(sweep, N_STEPS, TRACE_EVERY,
              config=FleetConfig(n_workers=2, n_shards=3),
              journal=journal)
    misses0 = SWEEP_EXEC_CACHE.stats().misses
    out = run_fleet(sweep, N_STEPS, TRACE_EVERY,
                    config=FleetConfig(n_workers=2, n_shards=3),
                    journal=journal)
    assert out.stats.executed == 0
    assert out.stats.resumed == len(out.plan.shards)
    assert SWEEP_EXEC_CACHE.stats().misses == misses0
    assert_bitwise(out.result, ref)


# ---------------------------------------------------------------------------
# scheduler semantics
# ---------------------------------------------------------------------------


def test_work_stealing_levels_ragged_shards(sweep, ref):
    """2 workers, 4 shards dealt LPT: the finisher steals the tail."""
    out = run_fleet(sweep, N_STEPS, TRACE_EVERY,
                    config=FleetConfig(n_workers=2, n_shards=4))
    assert_bitwise(out.result, ref)
    workers = {o.worker for o in out.outcomes.values()
               if isinstance(o, (Done, Retried))}
    assert len(workers) == 2, "one worker served the whole fleet"


def test_worker_lost_requeues_for_survivors(sweep, ref):
    killed = []

    def fault(shard, attempt, worker):
        if shard.index == 0 and not killed:
            killed.append(worker)
            raise WorkerLost("chaos")

    out = run_fleet(sweep, N_STEPS, TRACE_EVERY,
                    config=FleetConfig(n_workers=2, n_shards=3),
                    fault_hook=fault)
    assert killed
    assert_bitwise(out.result, ref)
    o = out.outcomes[0]
    assert isinstance(o, Retried) and o.worker != killed[0]


def test_retry_then_succeed(sweep, ref):
    attempts = []

    def fault(shard, attempt, worker):
        if shard.index == 0 and attempt == 1:
            attempts.append(attempt)
            raise RuntimeError("transient")

    out = run_fleet(sweep, N_STEPS, TRACE_EVERY,
                    config=FleetConfig(n_workers=2, n_shards=3,
                                       backoff_s=0.0),
                    fault_hook=fault)
    assert attempts
    o = out.outcomes[0]
    assert isinstance(o, Retried) and o.attempts == 2 and o.errors
    assert out.stats.retries == 1
    assert_bitwise(out.result, ref)


def test_abandoned_is_explicit_and_strict_raises(sweep):
    def fault(shard, attempt, worker):
        if shard.index == 0:
            raise RuntimeError("permanent")

    with pytest.raises(FleetError, match="abandoned"):
        run_fleet(sweep, N_STEPS, TRACE_EVERY,
                  config=FleetConfig(n_workers=2, n_shards=3,
                                     max_retries=1, backoff_s=0.0),
                  fault_hook=fault)

    out = run_fleet(sweep, N_STEPS, TRACE_EVERY,
                    config=FleetConfig(n_workers=2, n_shards=3,
                                       max_retries=1, backoff_s=0.0,
                                       strict=False),
                    fault_hook=fault)
    bad = out.abandoned
    assert len(bad) == 1 and bad[0].shard == 0
    assert bad[0].attempts == 2 and bad[0].errors
    # the merged result still covers every OTHER shard's points
    covered = {n for s in out.plan.shards if s.index != 0
               for n in s.names}
    assert {p.name for p in out.result.points} == covered


def test_all_workers_lost_abandons_remainder(sweep):
    def fault(shard, attempt, worker):
        raise WorkerLost("everyone dies")

    out = run_fleet(sweep, N_STEPS, TRACE_EVERY,
                    config=FleetConfig(n_workers=2, n_shards=3,
                                       strict=False),
                    fault_hook=fault)
    assert out.result is None
    assert all(isinstance(o, Abandoned) for o in out.outcomes.values())
    assert len(out.outcomes) == len(out.plan.shards)


def test_journal_rejects_foreign_plan(tmp_path, sweep):
    plan = plan_sweep(sweep, N_STEPS, TRACE_EVERY, n_shards=3)
    other = plan_sweep(sweep, N_STEPS * 2, TRACE_EVERY, n_shards=3)
    jr = FleetJournal(str(tmp_path))
    jr.bind(plan)
    with pytest.raises(ValueError, match="bound to plan"):
        jr.bind(other)


def test_journal_claims_are_exclusive(tmp_path):
    jr = FleetJournal(str(tmp_path))
    assert jr.claim("d1", "a")
    assert not jr.claim("d1", "b")
    assert jr.claim_age("d1") is not None
    jr.steal_claim("d1", "b")       # stale takeover is an overwrite
    jr.release("d1")
    assert jr.claim_age("d1") is None
    assert jr.failures("d1") == 0
    assert jr.record_failure("d1", "boom") == 1
    assert jr.record_failure("d1", "boom again") == 2
    assert jr.failures("d1") == 2


# ---------------------------------------------------------------------------
# multi-process (jax.distributed) leg
# ---------------------------------------------------------------------------

_DIST_CHILD = """
import sys
import jax
import numpy as np

port, pid, journal = sys.argv[1], int(sys.argv[2]), sys.argv[3]
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
assert jax.process_count() == 2

from repro.core import CCScheme, PAPER_CONFIG, ScenarioSpec, Sweep
from repro.core.serialize import _SIM_TRACE_FIELDS
from repro.fleet import (DistributedBackend, FleetConfig, FleetJournal,
                         FleetRunner, plan_sweep)

sweep = Sweep.grid(
    configs={s.name: PAPER_CONFIG.replace(scheme=s) for s in CCScheme},
    scenarios={"i2": ScenarioSpec.incast(2, victim=False),
               "hol": ScenarioSpec.paper_incast(roll=0)})
plan = plan_sweep(sweep, 300, 50, n_shards=3)
jr = FleetJournal(journal)
out = FleetRunner(plan, FleetConfig(claim_timeout_s=60.0,
                                    timeout_s=600.0),
                  backend=DistributedBackend(jr), journal=jr).run()
if pid == 0:
    assert out.stats.abandoned == 0, out.outcomes
    ref = sweep.run(n_steps=300, trace_every=50)
    res = out.result
    assert [p.name for p in res.points] == [p.name for p in ref.points]
    np.testing.assert_array_equal(res.times, ref.times)
    for f in _SIM_TRACE_FIELDS:
        a, b = getattr(res.traces, f), getattr(ref.traces, f)
        assert (a is None) == (b is None), f
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f)
    la = jax.tree_util.tree_flatten_with_path(res.final)[0]
    lb = jax.tree_util.tree_flatten_with_path(ref.final)[0]
    for (pa, ga), (_, gb) in zip(la, lb):
        assert np.array_equal(np.asarray(ga), np.asarray(gb)), \\
            "final" + jax.tree_util.keystr(pa)
    print("DIST_FLEET_BITWISE_OK")
"""


def _child_env() -> dict:
    env = dict(os.environ)
    src = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    # the parent may hold an accelerator; the children must never reach
    # for one
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_distributed_fleet_two_processes_bitwise(tmp_path):
    """2 jax.distributed processes level one journal-claimed queue; the
    coordinator's merged result is bitwise the single-host launch."""
    port = _free_port()
    journal = str(tmp_path / "journal")
    env = _child_env()
    procs = [subprocess.Popen(
        [sys.executable, "-c", _DIST_CHILD, str(port), str(pid), journal],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for pid in (0, 1)]
    outs = [p.communicate(timeout=1200) for p in procs]
    for p, (so, se) in zip(procs, outs):
        assert p.returncode == 0, f"proc exited {p.returncode}:\n" \
            f"{se[-3000:]}"
    assert "DIST_FLEET_BITWISE_OK" in outs[0][0]


def test_coordinator_reclaims_dead_workers_claim(tmp_path, sweep, ref):
    """A worker that died mid-shard leaves a dangling claim file (no
    release, no result).  The coordinator must steal the stale claim
    and run the shard itself — points are delayed, never dropped.
    Single-process: ``process_info`` falls back to (0, 1), so the same
    DistributedBackend code runs as the coordinator."""
    plan = plan_sweep(sweep, N_STEPS, TRACE_EVERY, n_shards=3)
    jr = FleetJournal(str(tmp_path / "journal"))
    jr.bind(plan)
    # fake the dead worker: claim shard 0's digest, backdate the claim
    # far past claim_timeout_s
    victim = plan.shards[0]
    assert jr.claim(victim.digest, "dead-proc")
    stale = os.path.join(jr.claims_dir, victim.digest)
    os.utime(stale, (1.0, 1.0))
    out = FleetRunner(plan, FleetConfig(claim_timeout_s=30.0,
                                        timeout_s=300.0, poll_s=0.05),
                      backend=DistributedBackend(jr), journal=jr).run()
    assert out.stats.abandoned == 0
    assert out.stats.stolen >= 1              # the reclaim happened
    assert_bitwise(out.result, ref)
