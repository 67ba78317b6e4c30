"""Bit-exact parity suite for the one-pass hot loop.

The fused segment-reduction rewrite and the Pallas per-flow kernels
must be *indistinguishable* from the legacy paths: the golden suite
freezes summaries, so even one reordered f32 add would show.  This
module pins the strongest form — exact array equality — across the
same 18-point scheme x fabric x routing grid the golden suite runs:

  * ``reduce="fused"``  vs ``reduce="scat"``  (segment sum vs scatter)
  * ``reduce="pallas"`` vs ``reduce="fused"`` (fluid_reduce kernel,
    interpret mode)
  * ``use_kernels=True`` vs jnp per-flow block (gen/np-timer + RP/ERP
    kernels, interpret mode)
  * ``use_kernels="mega"`` vs ``reduce="scat"`` (the whole-step
    megakernel, one launch per trace window, interpret mode)

plus unit-level checks of the incidence precompute and the
content-keyed device-placement cache.
"""

import jax
import numpy as np
import pytest

from repro.core import (CCScheme, CCSpec, PAPER_CONFIG, ScenarioSpec,
                        Sweep)
from repro.core.fluid import (_flow_jitter, init_state, make_step_fn,
                              scenario_device)
from repro.core.routing import PAD, link_incidence
from repro.core.workloads import group_shift
from repro.kernels.fluid_reduce import segment_reduce
from repro.net import FabricSpec

TRACE_FIELDS = ("delivered", "rate", "inst_thr", "max_q", "n_paused",
                "marked", "cnp", "n_nonmin", "ctrl", "pause_time",
                "vc_stall")


def _grid_scenarios() -> dict:
    dfly = FabricSpec.dragonfly(a=2, p=2, h=2)
    ft = FabricSpec.fat_tree(4, taper=2)
    return {
        "dfly_adv": group_shift(5, 4, t_stop=0.5e-3).spec(
            fabric=dfly, n_paths=4, route_seed=0, label="dfly_adv"),
        "ft_perm": ScenarioSpec.permutation(
            16, seed=2, fabric=ft, n_paths=4, route_seed=0,
            t_start=0.0, t_stop=0.5e-3, label="ft_perm"),
    }


def _grid() -> Sweep:
    """The golden suite's 18-point grid (same seeds/shapes)."""
    configs = {f"{s.name}/{r}": PAPER_CONFIG.replace(scheme=s, routing=r)
               for s in CCScheme for r in ("min", "valiant", "ugal")}
    return Sweep.grid(configs=configs, scenarios=_grid_scenarios())


def _assert_final_equal(fa, fb, ctx):
    """Exact leaf-wise equality of two FluidStates (dict-state aware)."""
    la = jax.tree_util.tree_flatten_with_path(fa)[0]
    lb = jax.tree_util.tree_flatten_with_path(fb)[0]
    assert len(la) == len(lb)
    for (pa, ga), (pb, gb) in zip(la, lb):
        assert pa == pb
        assert np.array_equal(np.asarray(ga), np.asarray(gb)), \
            ctx + (jax.tree_util.keystr(pa),)


def _assert_bitwise(res_a, res_b, ctx: str):
    assert res_a.names == res_b.names
    for name in res_a.names:
        a, b = res_a[name], res_b[name]
        for f in TRACE_FIELDS:
            ga, gb = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
            assert np.array_equal(ga, gb), (ctx, name, f)
        _assert_final_equal(a.final, b.final, (ctx, name, "final"))


def test_fused_matches_scat_on_golden_grid():
    """One sweep launch per engine; every decimated trace and the final
    state must agree to the bit across all 18 points."""
    sweep = _grid()
    _assert_bitwise(sweep.run(n_steps=150, reduce="fused"),
                    sweep.run(n_steps=150, reduce="scat"),
                    "fused-vs-scat")


def test_kernel_flow_block_matches_jnp_on_golden_grid():
    """Pallas gen/np-timer + RP/ERP kernels (interpret mode) vs the jnp
    per-flow block: exact f32 equality."""
    sweep = _grid()
    _assert_bitwise(
        sweep.run(n_steps=60),
        sweep.run(n_steps=60, use_kernels=True, interpret=True),
        "kernels-vs-jnp")


def test_megakernel_matches_scat_on_golden_grid():
    """The whole-step megakernel — every phase of the step plus the
    in-kernel trace-window scan inside one pallas_call — vs the scatter
    engine: exact equality of all decimated traces and final states
    (delay-line ring and per-flow CC state included) across the 18-point
    grid."""
    sweep = _grid()
    _assert_bitwise(
        sweep.run(n_steps=60, reduce="scat"),
        sweep.run(n_steps=60, use_kernels="mega", interpret=True),
        "mega-vs-scat")


def test_megakernel_matches_scat_at_two_vcs():
    """The megakernel carries the per-VC queue axis (and its stall
    trace) bit-exactly too."""
    sweep = _grid_v2()
    _assert_bitwise(
        sweep.run(n_steps=60, reduce="scat"),
        sweep.run(n_steps=60, use_kernels="mega", interpret=True),
        "mega-vs-scat-v2")


def test_simulator_run_megakernel_bitexact():
    """``simulator.run(use_kernels="mega")`` — the single-point entry —
    matches the per-step scat path sample for sample."""
    from repro.core import simulator as sim
    cfg = PAPER_CONFIG
    scn = ScenarioSpec.paper_incast(
        roll=0, t_start=0.1e-3, t_stop=1.2e-3).build(cfg)
    ra = sim.run(scn, cfg, n_steps=60, trace_every=10, reduce="scat")
    rb = sim.run(scn, cfg, n_steps=60, trace_every=10,
                 use_kernels="mega", interpret=True)
    for f in TRACE_FIELDS:
        assert np.array_equal(np.asarray(getattr(ra, f)),
                              np.asarray(getattr(rb, f))), f
    _assert_final_equal(ra.final, rb.final, ("sim-mega",))


def test_megakernel_rejects_nested_pallas_reduce():
    """reduce="pallas" cannot run inside the megakernel (no nested
    pallas_call); the combination is refused up front."""
    cfg = PAPER_CONFIG
    scn = ScenarioSpec.paper_incast(roll=0).build(cfg)
    with pytest.raises(ValueError, match="mega"):
        make_step_fn(scn, cfg, reduce="pallas", use_kernels="mega",
                     interpret=True)


def test_kernel_tier_rejects_unknown_string():
    from repro.core.fluid import kernel_tier
    assert kernel_tier(False) == "off"
    assert kernel_tier(True) == "flow"
    assert kernel_tier("mega") == "mega"
    with pytest.raises(ValueError, match="use_kernels"):
        kernel_tier("turbo")


@pytest.mark.parametrize("tier", [True, "mega"])
def test_soft_gates_refused_under_kernels_at_both_entry_points(tier):
    """temperature > 0 + any kernel tier must raise at *both* entry
    points (``make_step_fn`` and ``fluid_step``), not silently run the
    hard dynamics (the kernels implement the hard model only)."""
    from repro.core.fluid import fluid_step, step_params
    cfg = PAPER_CONFIG
    scn = ScenarioSpec.paper_incast(roll=0).build(cfg)
    with pytest.raises(ValueError, match="temperature"):
        make_step_fn(scn, cfg, use_kernels=tier, interpret=True,
                     temperature=0.1)
    st = init_state(scn, cfg)
    sd = scenario_device(scn)
    par = step_params(cfg, temperature=0.1)
    with pytest.raises(ValueError, match="temperature"):
        fluid_step(st, sd, par, dt=float(cfg.sim.dt),
                   n_switches=int(scn.n_switches), use_kernels=tier,
                   interpret=True)
    # temperature=0 through the same entry points is fine
    make_step_fn(scn, cfg, use_kernels=tier, interpret=True,
                 temperature=0.0)


def test_pallas_reduce_matches_fused_single_point():
    """The fluid_reduce kernel inside a real stepping loop."""
    cfg = PAPER_CONFIG.replace(routing="ugal")
    scn = ScenarioSpec.permutation(
        16, seed=2, fabric=FabricSpec.fat_tree(4, taper=2), n_paths=4,
        route_seed=0, t_start=0.0, t_stop=0.5e-3).build(cfg)
    outs = []
    for kw in (dict(reduce="fused"),
               dict(reduce="pallas", interpret=True)):
        step = jax.jit(make_step_fn(scn, cfg, **kw))
        st = init_state(scn, cfg)
        for _ in range(100):
            st, _ = step(st)
        outs.append(st)
    _assert_final_equal(outs[0], outs[1], ("pallas-vs-fused",))


# ---------------------------------------------------------------------------
# legacy-scheme shim parity: CCConfig == hand-written CCSpec, bit for bit
# ---------------------------------------------------------------------------

#: what each legacy scheme must decompose into (the shim's contract)
SCHEME_STAGES = {
    CCScheme.PFC_ONLY: ("cp", "np", "pfc"),
    CCScheme.DCQCN: ("cp", "np", "rp"),
    CCScheme.DCQCN_REV: ("ecp", "enp", "erp"),
}


# ---------------------------------------------------------------------------
# multi-VC parity: the per-VC queue axis through every engine
# ---------------------------------------------------------------------------

def _grid_v2() -> Sweep:
    """The 18-point grid at n_vcs=2 (detour hops land on VC 1, so the
    valiant/ugal points exercise genuinely split lanes)."""
    from repro.core.params import LinkParams
    link = LinkParams(n_vcs=2)
    configs = {}
    for s, (m, n, r) in SCHEME_STAGES.items():
        for routing in ("min", "valiant", "ugal"):
            configs[f"{s.name}/{routing}"] = CCSpec(
                marking=m, notification=n, reaction=r, routing=routing,
                link=link)
    return Sweep.grid(configs=configs, scenarios=_grid_scenarios())


def test_fused_matches_scat_at_two_vcs():
    """The VC-striped incidence reduces identically through segment-sum
    and scatter — traces (incl. per-VC stall) and final state."""
    sweep = _grid_v2()
    _assert_bitwise(sweep.run(n_steps=150, reduce="fused"),
                    sweep.run(n_steps=150, reduce="scat"),
                    "fused-vs-scat-v2")


def test_kernel_flow_block_matches_jnp_at_two_vcs():
    sweep = _grid_v2()
    _assert_bitwise(
        sweep.run(n_steps=60),
        sweep.run(n_steps=60, use_kernels=True, interpret=True),
        "kernels-vs-jnp-v2")


def test_pallas_reduce_matches_fused_at_two_vcs():
    from repro.core.params import LinkParams
    cfg = CCSpec(routing="ugal", link=LinkParams(n_vcs=2))
    scn = ScenarioSpec.permutation(
        16, seed=2, fabric=FabricSpec.fat_tree(4, taper=2), n_paths=4,
        route_seed=0, t_start=0.0, t_stop=0.5e-3).build(cfg)
    outs = []
    for kw in (dict(reduce="fused"),
               dict(reduce="pallas", interpret=True)):
        step = jax.jit(make_step_fn(scn, cfg, **kw))
        st = init_state(scn, cfg)
        for _ in range(100):
            st, _ = step(st)
        outs.append(st)
    _assert_final_equal(outs[0], outs[1], ("pallas-vs-fused-v2",))


def test_single_vc_link_params_is_inert():
    """Spelling ``n_vcs=1`` explicitly is the identity — same bits as
    the default config on a golden-grid point (the V axis collapses to
    the legacy layout, not a parallel code path)."""
    from repro.core.params import LinkParams
    spec = _grid_scenarios()["dfly_adv"]
    base = CCSpec(routing="ugal")
    expl = CCSpec(routing="ugal", link=LinkParams(n_vcs=1))
    _assert_bitwise(
        Sweep.grid(configs={"p": base}, scenarios={"s": spec}).run(
            n_steps=150),
        Sweep.grid(configs={"p": expl}, scenarios={"s": spec}).run(
            n_steps=150),
        "v1-inert")


def test_legacy_shim_bitexact_on_golden_grid():
    """Every legacy CCScheme x routing point must produce the same bits
    through an *explicitly constructed* CCSpec as through the CCConfig
    shim — one sweep launch each, traces AND final state compared."""
    legacy = _grid()
    spec_configs = {}
    for s in CCScheme:
        m, n, r = SCHEME_STAGES[s]
        for routing in ("min", "valiant", "ugal"):
            spec_configs[f"{s.name}/{routing}"] = CCSpec(
                marking=m, notification=n, reaction=r, routing=routing)
    explicit = Sweep.grid(configs=spec_configs,
                          scenarios=_grid_scenarios())
    _assert_bitwise(legacy.run(n_steps=150), explicit.run(n_steps=150),
                    "shim-vs-spec")


def test_legacy_override_shim_bitexact():
    """The marking/reaction ablation overrides map through the registry
    bit-exactly too (including the PFC_ONLY window quirk: notification
    follows the reaction override even when the reaction is pinned)."""
    spec_scn = ScenarioSpec.paper_incast(roll=0, t_start=0.1e-3)
    cases = {
        "ecp_rp": (PAPER_CONFIG.replace(scheme=CCScheme.DCQCN,
                                        marking="ecp"),
                   CCSpec(marking="ecp", notification="np",
                          reaction="rp")),
        "cp_erp": (PAPER_CONFIG.replace(scheme=CCScheme.DCQCN,
                                        reaction="erp"),
                   CCSpec(marking="cp", notification="enp",
                          reaction="erp")),
        "pfc_erp": (PAPER_CONFIG.replace(scheme=CCScheme.PFC_ONLY,
                                         reaction="erp"),
                    CCSpec(marking="cp", notification="enp",
                           reaction="pfc")),
    }
    legacy = Sweep([(k, cfg, spec_scn) for k, (cfg, _) in cases.items()])
    explicit = Sweep([(k, sp, spec_scn) for k, (_, sp) in cases.items()])
    _assert_bitwise(legacy.run(n_steps=1500),
                    explicit.run(n_steps=1500), "override-shim")


# ---------------------------------------------------------------------------
# segment_reduce kernel unit tests
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,c,s", [(1, 1, 1), (100, 3, 17), (513, 2, 5),
                                   (1536, 8, 300), (4096, 5, 1000)])
def test_segment_reduce_exact(n, c, s):
    rng = np.random.RandomState(n + c + s)
    seg = np.sort(rng.randint(0, s, size=n)).astype(np.int32)
    data = rng.randn(n, c).astype(np.float32)
    got = segment_reduce(jax.numpy.asarray(data), jax.numpy.asarray(seg),
                         s, interpret=True)
    want = jax.ops.segment_sum(jax.numpy.asarray(data),
                               jax.numpy.asarray(seg), num_segments=s,
                               indices_are_sorted=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_segment_reduce_empty_input():
    """Zero rows must yield exact zeros (the grid never runs, so the
    wrapper must not hand back uninitialised output)."""
    out = segment_reduce(jax.numpy.zeros((0, 3), jax.numpy.float32),
                         jax.numpy.zeros((0,), jax.numpy.int32), 7,
                         interpret=True)
    assert np.array_equal(np.asarray(out), np.zeros((7, 3), np.float32))


def test_segment_reduce_rejects_oversized_accumulator():
    """Shapes whose [S, C] accumulator cannot sit in VMEM are refused
    with a pointer at the segment-sum engine, not silently compiled."""
    with pytest.raises(ValueError, match="VMEM"):
        segment_reduce(jax.numpy.zeros((512, 128), jax.numpy.float32),
                       jax.numpy.zeros((512,), jax.numpy.int32),
                       1 << 16, interpret=True)


def test_segment_reduce_empty_segments():
    """Links no flow crosses must come back exactly 0."""
    seg = np.asarray([3, 3, 7], np.int32)
    data = np.ones((3, 2), np.float32)
    out = np.asarray(segment_reduce(jax.numpy.asarray(data),
                                    jax.numpy.asarray(seg), 10,
                                    interpret=True))
    want = np.zeros((10, 2), np.float32)
    want[3] = 2.0
    want[7] = 1.0
    assert np.array_equal(out, want)


# ---------------------------------------------------------------------------
# incidence precompute + device-placement cache
# ---------------------------------------------------------------------------

def test_link_incidence_structure():
    rng = np.random.RandomState(0)
    F, K, H, L = 13, 3, 5, 40
    alt = rng.randint(-1, L, size=(F, K, H)).astype(np.int32)
    perm, seg, off = link_incidence(alt, L)
    assert sorted(perm.tolist()) == list(range(F * K * H))
    assert (np.diff(seg) >= 0).all()                  # sorted
    flat = alt.reshape(-1)
    np.testing.assert_array_equal(
        seg, np.where(flat[perm] == PAD, L, flat[perm]))
    # CSR offsets: segment l spans [off[l], off[l+1])
    assert off[0] == 0 and off[-1] == F * K * H
    for l in (0, L // 2, L):                          # spot-check rows
        rows = perm[off[l]:off[l + 1]]
        vals = np.where(flat == PAD, L, flat)[rows]
        assert (vals == l).all()
    # stability: equal-id entries keep flattened order
    for l in range(L + 1):
        assert (np.diff(perm[off[l]:off[l + 1]]) > 0).all()


def test_clamp_dense_rows_guards_batch_max():
    """The dense-CSR size guard applies to batch-wide row counts too:
    a skewed maximum that would dwarf the incidence disables the dense
    engine instead of inflating every run's table."""
    from repro.core.fluid import DENSE_ROWS_CAP, clamp_dense_rows
    assert clamp_dense_rows(4, 384, 30) == 4
    assert clamp_dense_rows(0, 384, 30) == 0
    assert clamp_dense_rows(DENSE_ROWS_CAP + 1, 10, 10 ** 9) == 0
    # L * ml far beyond 16x the incidence entries -> disabled
    assert clamp_dense_rows(1000, 100_000, 6_000) == 0


def test_fabric_incidence_mirrors_scenario_device():
    """RouteTable/RouteSet.incidence are the host-side view of the
    exact ``red_*`` layout ``scenario_device`` ships: same permutation,
    segments and CSR offsets for the same pairs."""
    fab = FabricSpec.fat_tree(4, taper=2)
    pairs = [(0, 9), (3, 17), (22, 41), (5, 60), (13, 2)]
    for spec, inc in [
            (ScenarioSpec.flows(pairs, fabric=fab),
             lambda L: fab.route_table().incidence(L, pairs)),
            (ScenarioSpec.flows(pairs, fabric=fab, n_paths=4,
                                route_seed=0),
             lambda L: fab.route_set(4, seed=0).incidence(L, pairs))]:
        scn = spec.build(PAPER_CONFIG)
        sd = scenario_device(scn)
        perm, seg, off = inc(scn.capacity.shape[0])
        np.testing.assert_array_equal(np.asarray(sd.red_perm), perm)
        np.testing.assert_array_equal(np.asarray(sd.red_seg), seg)
        np.testing.assert_array_equal(np.asarray(sd.red_off), off)


def test_scenario_device_upload_cache_and_jitter():
    """Two grid points sharing a fabric must share the device buffers
    of its route/capacity tensors (content-keyed placement cache), and
    the ERP jitter must be hoisted into the scenario."""
    cfg = PAPER_CONFIG
    spec = ScenarioSpec.paper_incast(roll=0)
    sd1 = scenario_device(spec.build(cfg))
    sd2 = scenario_device(spec.build(cfg.replace(scheme=CCScheme.DCQCN)))
    for f in ("cap_ext", "sink_ext", "alt_routes", "alt_hops",
              "red_perm", "red_seg", "red_off", "pool_perm", "pool_seg",
              "jitter"):
        assert getattr(sd1, f) is getattr(sd2, f), f
    F = sd1.gen_rate.shape[0]
    np.testing.assert_array_equal(np.asarray(sd1.jitter), _flow_jitter(F))


# ---------------------------------------------------------------------------
# the jagged dense layout: parity, and the layout built on the host
# ---------------------------------------------------------------------------

def _skew_batch() -> Sweep:
    """Two runs of one fabric whose skew differs: an incast of 12 hosts
    onto host 0's down-link, and a uniform permutation."""
    ft = FabricSpec.fat_tree(4, taper=2)
    incast = ScenarioSpec.flows([(i, 0) for i in range(1, 13)], fabric=ft,
                                t_start=0.0, t_stop=0.5e-3)
    uniform = ScenarioSpec.permutation(16, seed=3, fabric=ft, t_start=0.0,
                                       t_stop=0.5e-3)
    return Sweep.grid(
        configs={s.name: PAPER_CONFIG.replace(scheme=s) for s in CCScheme},
        scenarios={"incast": incast, "uniform": uniform})


def _multipath_grid() -> Sweep:
    cfgs = {r: PAPER_CONFIG.replace(scheme=CCScheme.DCQCN_REV, routing=r)
            for r in ("valiant", "ugal")}
    return Sweep.grid(configs=cfgs,
                      scenarios={"ft_perm": _grid_scenarios()["ft_perm"]})


@pytest.mark.parametrize("batch", [_grid, _grid_v2, _multipath_grid,
                                   _skew_batch],
                         ids=["golden", "two_vcs", "multipath", "mixed_skew"])
def test_jagged_layout_matches_scat(batch):
    """The derived layout is jagged (several blocks, fewer slots than
    the rectangle of every queue x the longest list) and the sweep it
    runs is bitwise the scatter engine's."""
    sweep = batch()
    static, (_, sd, _), _, _ = sweep._prepare(60, 10)
    blocks = static[5]
    S = (sd.cap_ext.shape[1] - 1) * sweep.n_vcs
    assert len(blocks) > 1
    assert sd.red_idx.shape[1] == sum(w * n for w, n in blocks)
    assert sd.red_idx.shape[1] < S * sum(n for _, n in blocks)
    _assert_bitwise(sweep.run(n_steps=60), sweep.run(n_steps=60,
                                                     reduce="scat"),
                    "jagged-vs-scat")


def test_mixed_skew_pads_each_position_to_the_widest_run():
    """Each position is as wide as the run with the most queues that
    long; the incast run alone reaches the last positions."""
    from repro.core.experiments import stack_scenarios
    from repro.core.fluid import dense_layout, jagged_blocks
    _, padded, _ = stack_scenarios([p.scenario for p in
                                    _skew_batch().points])
    blocks, idx, back, n_rows = dense_layout(padded)
    counts = []
    for s in padded:
        perm, _, off = link_incidence(s.routes[:, None, :],
                                      s.capacity.shape[0])
        counts.append(np.diff(off)[:-1])
    counts = np.stack(counts)
    assert blocks == jagged_blocks(counts)
    assert sum(n for _, n in blocks) == counts.max() == 12
    assert blocks[-1][0] == 1                     # the incast link alone
    assert n_rows == counts.sum()


def _random_incidence(rng, F, K, H, L, hot):
    """A [F, K, H] candidate stack whose hops pile onto ``hot`` links
    with probability one half, PAD-padded at random lengths."""
    alt = np.where(rng.random((F, K, H)) < 0.5,
                   rng.integers(0, hot, (F, K, H)),
                   rng.integers(0, L, (F, K, H)))
    hops = rng.integers(1, H + 1, (F, K, 1))
    return np.where(np.arange(H)[None, None, :] < hops, alt,
                    PAD).astype(np.int32)


def _queue_lists(idx, back, blocks, N):
    """Each queue's contributor rows, read back out of the layout in
    slot order (sentinels dropped)."""
    lists = {}
    start = 0
    for w, n in blocks:
        tab = idx[start:start + w * n].reshape(n, w)
        start += w * n
        for q, r in enumerate(back[:-1]):
            if r < w:
                lists.setdefault(q, []).extend(v for v in tab[:, r] if v != N)
    return lists


@pytest.mark.parametrize("seed", range(6))
def test_jagged_index_holds_each_contributor_once_in_order(seed):
    """Host layout: every contributor appears exactly once, each queue's
    contributors in ascending (incidence) order, every other slot is the
    sentinel, and the slots stay within twice the positions' widths."""
    from repro.core.fluid import jagged_blocks, jagged_index
    rng = np.random.default_rng(seed)
    F, K, H, L = 40, 1 + seed % 3, 5, 30
    runs = [link_incidence(_random_incidence(rng, F, K, H, L, 1 + r), L)
            for r in range(3)]
    counts = np.stack([np.diff(off)[:L] for _, _, off in runs])
    blocks = jagged_blocks(counts)
    widths = [int((counts > p).sum(axis=1).max())
              for p in range(counts.max())]
    assert sum(n for _, n in blocks) == len(widths)
    total = sum(w * n for w, n in blocks)
    assert total <= 2 * sum(widths)
    N = F * K * H
    for (perm, _, off), c in zip(runs, counts):
        idx, back = jagged_index(perm, off, L, blocks)
        assert idx.shape == (total,) and back.shape == (L + 1,)
        assert back[L] == blocks[0][0]                    # scratch -> zero
        lists = _queue_lists(idx, back, blocks, N)
        for q in range(L):
            want = list(perm[off[q]:off[q + 1]])
            assert lists.get(q, []) == want, q
            assert want == sorted(want)
        real = np.sort(idx[idx != N])
        np.testing.assert_array_equal(real, np.sort(perm[:off[L]]))
        assert np.count_nonzero(idx == N) == total - c.sum()


def test_jagged_index_refuses_a_layout_too_short():
    from repro.core.fluid import jagged_index
    alt = np.zeros((3, 1, 1), np.int32)               # 3 flows on link 0
    perm, _, off = link_incidence(alt, 2)
    with pytest.raises(ValueError, match="cannot hold"):
        jagged_index(perm, off, 2, ((2, 2),))


def test_pinned_dense_rows_compiles_once_across_contents():
    """A pinned ``dense_rows`` is the one-block rectangle: batches of
    different content (and skew) share one executable."""
    from repro.core.experiments import SWEEP_EXEC_CACHE
    ft = FabricSpec.fat_tree(4, taper=2)
    cfg = PAPER_CONFIG.replace(scheme=CCScheme.DCQCN)
    batches = [
        Sweep([("a", cfg, ScenarioSpec.flows(
            [(i, 0) for i in range(1, 9)], fabric=ft))]),
        Sweep([("b", cfg, ScenarioSpec.permutation(8, seed=5, fabric=ft))]),
    ]
    kw = dict(dense_rows=12, min_delay_slots=16)
    prepared = [b._prepare(20, 10, **kw) for b in batches]
    L = prepared[0][1][1].cap_ext.shape[-1] - 1
    assert prepared[0][0] == prepared[1][0]
    assert prepared[0][0][5] == ((L, 12),)
    misses0 = SWEEP_EXEC_CACHE.stats().misses
    runs = [b.run(n_steps=20, **kw) for b in batches]
    assert SWEEP_EXEC_CACHE.stats().misses - misses0 <= 1
    for b, r in zip(batches, runs):
        _assert_bitwise(r, b.run(n_steps=20, reduce="scat",
                                 min_delay_slots=16), "pinned")


def test_dense_layout_is_memoised_on_content():
    """Relaunching a batch stages nothing new: the layout and its device
    arrays come back from the cache."""
    from repro.core.experiments import stack_scenarios
    from repro.core.fluid import dense_layout
    _, padded, _ = stack_scenarios([p.scenario for p in
                                    _skew_batch().points])
    a, b = dense_layout(padded), dense_layout(list(padded))
    assert all(x is y for x, y in zip(a, b))
    pinned = dense_layout(padded, rows=12)
    assert pinned[0] == ((padded[0].capacity.shape[0], 12),)
    assert pinned[1] is not a[1]


# ---------------------------------------------------------------------------
# per-hop reads: one gather per data-dependency phase
# ---------------------------------------------------------------------------

def _hop_gathers(jaxpr, F, H, stack=""):
    """Name stacks of the gathers in ``jaxpr`` (and its sub-jaxprs)
    whose result ends in the per-hop shape ``[F, H]`` (``[F, H]`` from
    one vector, ``[C, F, H]`` from C stacked ones)."""
    found = []
    for eqn in jaxpr.eqns:
        names = f"{stack}/{eqn.source_info.name_stack}"
        if (eqn.primitive.name == "gather"
                and eqn.outvars[0].aval.shape[-2:] == (F, H)):
            found.append(names)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _hop_gathers(inner, F, H, names)
    return found


@pytest.mark.parametrize("multipath", [False, True],
                         ids=["k1_v1", "k4_v2"])
def test_step_reads_hop_values_in_four_gathers(multipath):
    """Each phase's per-queue and per-wire values reach the flows' hops
    through one gather of a ``[C, S + 1]`` table: four a step, whatever
    K and V (UGAL's candidate reads under ``fluid.select`` aside)."""
    from repro.core.params import LinkParams
    if multipath:
        cfg = CCSpec(routing="ugal", link=LinkParams(n_vcs=2))
        kw = dict(n_paths=4, route_seed=0)
    else:
        cfg, kw = PAPER_CONFIG, {}
    scn = ScenarioSpec.permutation(
        16, seed=2, fabric=FabricSpec.fat_tree(4, taper=2), **kw).build(cfg)
    st = init_state(scn, cfg)
    F, _, H = scenario_device(scn, n_vcs=2 if multipath else 1
                              ).alt_routes.shape
    jaxpr = jax.make_jaxpr(make_step_fn(scn, cfg))(st).jaxpr
    hop = [s for s in _hop_gathers(jaxpr, F, H) if "fluid.select" not in s]
    assert len(hop) == 4, hop
