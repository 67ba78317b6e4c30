"""Declarative Experiment/Sweep API: one-jit batched CC evaluation.

The paper's claims are sweep-shaped — scheme x scenario x parameter
grids — but a python loop of ``run()`` calls re-jits and re-launches per
point.  This module makes the sweep itself the unit of execution:

  * ``ScenarioSpec``   — declarative description of a workload (topology
    + traffic pattern + timing/volume).  ``spec.build(cfg)`` compiles it
    to the padded ``Scenario`` tensors of the fluid model.  The legacy
    builder functions in ``scenarios.py`` are thin wrappers over specs.
  * ``pad_scenario`` / stacking — N scenarios are padded to a common
    [F_max, H_max] (and link/switch counts) so they stack into one
    batched ``ScenarioDev`` pytree.  PAD flows/links are inert by
    construction (zero demand, infinite start time).
  * ``Sweep``          — N (config, scenario) points executed under ONE
    jitted vmap-of-scan: scheme ablations, Kmin/ERP-gain grids and
    incast-degree scans are single device launches.  Traces are
    decimated on device (``trace_every``), and the delay line is sized
    from the batch's worst-case RTT instead of a fixed cap.

Quickstart::

    from repro.core import CCScheme, PAPER_CONFIG
    from repro.core.experiments import ScenarioSpec, Sweep

    sweep = Sweep.grid(
        configs={s.name: PAPER_CONFIG.replace(scheme=s) for s in CCScheme},
        scenarios={"hol": ScenarioSpec.paper_incast(roll=0),
                   "disjoint": ScenarioSpec.paper_incast(roll=1)})
    res = sweep.run()                       # ONE compile, ONE launch
    res["DCQCN_REV/hol"].mean_throughput_while_active()
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import cc, obs
from .exec_cache import ExecutableCache, structural_signature
from .fluid import (FluidState, Scenario, check_routing_paths,
                    clamp_dense_rows, delay_depth, dense_engine,
                    dense_reduce_rows, fluid_step, init_state,
                    kernel_tier, scenario_device, step_body_fn,
                    step_params)
from .params import CCConfig, CCSpec
from .routing import PAD, route_hops
from .simulator import (SimResult, _acc_update, _resolve_steps,
                        _window_sample, _zero_accum, decimating_scan)
from .topology import Topology

if TYPE_CHECKING:           # real import is lazy: repro.net imports core
    from repro.net import FabricSpec


# ---------------------------------------------------------------------------
# ScenarioSpec — declarative workload description
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """Fabric + traffic pattern + timing/volume, as plain data.

    ``kind`` selects the traffic pattern:
      * ``"incast"``      — ``n_senders``-to-1 into ``dst`` (+ optional
        victim flow), the paper's §II scene when n_senders=4 on arity 4.
      * ``"permutation"`` — seeded uniform random permutation traffic.
      * ``"pairs"``       — explicit (src, dst) pairs.
      * ``"flowspec"``    — fully explicit per-flow tuples (src, dst,
        timing, volume, rate, buffer) — what the collective-workload
        generators in ``repro.core.workloads`` emit.

    ``fabric`` names the network (any ``repro.net.FabricSpec``: CLOS,
    XGFT/tapered fat-tree, dragonfly); ``None`` keeps the legacy
    3-stage CLOS of ``arity``/``roll``.  Routing is table-driven for
    every fabric — the CLOS closed form is just one table builder.

    Timing: generators open at ``t_start`` and close at ``t_stop``
    (window mode) — or carry ``volume`` bytes each and stay open until
    done (equal-work mode, ``t_stop = inf``), the variant behind the
    paper's completion-time ordering.

    ``build(cfg)`` compiles the spec to ``Scenario`` tensors; rates and
    feedback delays derive from ``cfg.link`` / ``cfg.sim``.
    """

    kind: str = "incast"
    fabric: "FabricSpec | None" = None
    arity: int = 4
    roll: int = 0                 # D-mod-K digit roll (paper wirings)
    n_senders: int = 4
    dst: int = 16
    victim: tuple[int, int] | None = (3, 12)
    pairs: tuple[tuple[int, int], ...] = ()
    n_flows: int = 16             # permutation
    seed: int = 0
    t_start: float = 1e-3
    t_stop: float = 3e-3          # inf => volume (equal-work) mode
    volume: float = float("inf")  # bytes per flow; inf = window-limited
    nic_buffer: float = 4e6
    gen_rate: float | None = None  # B/s; None = line rate
    label: str = ""
    # adaptive routing: K candidate paths per flow (slot 0 minimal,
    # 1..K-1 Valiant detours from the fabric's RouteSet).  Which
    # candidate a flow actually uses is the *config's* choice
    # (``cfg.routing`` in {min, valiant, ugal}), so one multi-path
    # scenario serves a whole routing-mode sweep axis.
    n_paths: int = 1
    route_seed: int = 0           # VLB intermediate sampling seed
    # virtual channels: how flows map onto the config's
    # ``LinkParams.n_vcs`` queues ("slot" = detours on VC 1, "hop" =
    # dateline escalation — see ``repro.core.routing.assign_vc``).
    # Ignored (all VC 0) when the config runs a single VC.
    vc_mode: str = "slot"
    # per-flow tuples (kind == "flowspec"); empty = broadcast the scalar
    flow_src: tuple[int, ...] = ()
    flow_dst: tuple[int, ...] = ()
    flow_t_start: tuple[float, ...] = ()
    flow_t_stop: tuple[float, ...] = ()
    flow_volume: tuple[float, ...] = ()
    flow_rate: tuple[float, ...] = ()          # B/s; empty = gen_rate
    flow_nic_buffer: tuple[float, ...] = ()    # B; empty = nic_buffer
    # per-flow VC pin (overrides vc_mode on every hop; clipped to the
    # config's n_vcs) and victim-flow designation for the PFC-pathology
    # metrics (``SimResult.victim_slowdown``); empty = none
    flow_vc: tuple[int, ...] = ()
    flow_victim: tuple[bool, ...] = ()

    # -- canned specs -------------------------------------------------------

    @classmethod
    def paper_incast(cls, roll: int = 0, **kw) -> "ScenarioSpec":
        """The paper's §II.A scene: F0,F1,F4,F8 -> N16 plus the victim
        F3 -> N12.  roll=0 shares the victim's wire (Fig. 3 HoL); roll=1
        is wire-disjoint (Fig. 2's 25 GB/s aggregate)."""
        return cls(kind="pairs",
                   pairs=((0, 16), (1, 16), (4, 16), (8, 16), (3, 12)),
                   roll=roll, label=kw.pop("label", f"paper-roll{roll}"),
                   flow_victim=kw.pop("flow_victim",
                                      (False,) * 4 + (True,)),
                   **kw)

    @classmethod
    def paper_incast_volume(cls, roll: int = 0,
                            volume_bytes: float = 9.375e6,
                            **kw) -> "ScenarioSpec":
        """Equal-work variant for completion-time runs (each flow carries
        the 9.375 MB a fair-shared incast source admits in 1->3 ms)."""
        return cls(kind="pairs",
                   pairs=((0, 16), (1, 16), (4, 16), (8, 16), (3, 12)),
                   roll=roll, t_stop=float("inf"), volume=volume_bytes,
                   nic_buffer=kw.pop("nic_buffer", 2 * volume_bytes),
                   label=kw.pop("label", f"paper-vol-roll{roll}"),
                   flow_victim=kw.pop("flow_victim",
                                      (False,) * 4 + (True,)),
                   **kw)

    @classmethod
    def incast(cls, n_senders: int, dst: int = 16, *, victim: bool = True,
               **kw) -> "ScenarioSpec":
        return cls(kind="incast", n_senders=n_senders, dst=dst,
                   victim=(3, 12) if victim else None,
                   label=kw.pop("label", f"incast{n_senders}"), **kw)

    @classmethod
    def permutation(cls, n_flows: int, seed: int = 0, **kw) -> "ScenarioSpec":
        kw.setdefault("t_start", 0.1e-3)
        kw.setdefault("t_stop", 2e-3)
        return cls(kind="permutation", n_flows=n_flows, seed=seed,
                   label=kw.pop("label", f"perm{n_flows}"), **kw)

    @classmethod
    def flows(cls, pairs: Sequence[tuple[int, int]], **kw) -> "ScenarioSpec":
        return cls(kind="pairs", pairs=tuple(tuple(p) for p in pairs),
                   label=kw.pop("label", f"pairs{len(pairs)}"), **kw)

    @classmethod
    def from_workload(cls, wl, fabric: "FabricSpec | None" = None,
                      **kw) -> "ScenarioSpec":
        """Compile a ``repro.core.workloads.Workload`` onto a fabric.

        The workload's per-flow (src, dst, timing, volume, rate) tuples
        become a ``"flowspec"`` spec; NIC buffers default to twice each
        flow's volume (volume mode) or the scalar ``nic_buffer``.
        """
        nic = kw.pop("flow_nic_buffer", None)
        if nic is None and any(np.isfinite(v) for v in wl.volume):
            nic = tuple(2 * v if np.isfinite(v) else kw.get(
                "nic_buffer", 4e6) for v in wl.volume)
        return cls(kind="flowspec", fabric=fabric,
                   flow_src=wl.src, flow_dst=wl.dst,
                   flow_t_start=wl.t_start, flow_t_stop=wl.t_stop,
                   flow_volume=wl.volume,
                   flow_rate=wl.rate or (),
                   flow_nic_buffer=nic or (),
                   flow_victim=kw.pop(
                       "flow_victim", getattr(wl, "victim", ()) or ()),
                   flow_vc=kw.pop(
                       "flow_vc", getattr(wl, "vc", ()) or ()),
                   label=kw.pop("label", wl.label), **kw)

    # -- compilation to tensors --------------------------------------------

    @property
    def name(self) -> str:
        return self.label or self.kind

    def _fabric(self) -> "FabricSpec":
        if self.fabric is not None:
            return self.fabric
        from repro.net import FabricSpec
        return FabricSpec.clos3(arity=self.arity, roll=self.roll)

    def _pairs(self, topo: Topology) -> list[tuple[int, int]]:
        if self.kind == "flowspec":
            if len(self.flow_src) != len(self.flow_dst):
                raise ValueError("flow_src / flow_dst length mismatch")
            return list(zip(self.flow_src, self.flow_dst))
        if self.kind == "pairs":
            return [tuple(p) for p in self.pairs]
        if self.kind == "incast":
            senders = [n for n in range(topo.n_nodes) if n != self.dst]
            out = [(s, self.dst) for s in senders[: self.n_senders]]
            if self.victim is not None:
                out.append(tuple(self.victim))
            return out
        if self.kind == "permutation":
            rng = np.random.RandomState(self.seed)
            n = topo.n_nodes
            perm = rng.permutation(n)
            srcs = rng.choice(n, size=self.n_flows,
                              replace=self.n_flows > n)
            out = []
            for s in srcs:
                d = int(perm[s % n])
                if d == s:
                    d = (d + 1) % n
                out.append((int(s), d))
            return out
        raise ValueError(f"unknown ScenarioSpec kind: {self.kind!r}")

    def _per_flow(self, field: tuple, scalar, F: int,
                  dtype=np.float32) -> np.ndarray:
        if field:
            if len(field) != F:
                raise ValueError(
                    f"per-flow tuple has {len(field)} entries for {F} flows")
            return np.asarray(field, dtype)
        return np.full((F,), scalar, dtype)

    @obs.span("repro.scenario.build")
    def build(self, cfg: CCConfig) -> Scenario:
        fab = self._fabric()
        topo = fab.build(line_rate=cfg.link.line_rate)
        pairs = self._pairs(topo)
        # the general routing path: every fabric family precomputes a
        # validated per-(src,dst) table; scenarios route by lookup.
        # n_paths > 1 pulls the fabric's multi-path RouteSet instead:
        # slot 0 (minimal) fills the legacy single-path tensors, the
        # full candidate stack rides along for run-time selection.
        # flow_routes / flow_route_set are cached per (spec hash, pairs):
        # every grid point sharing a fabric reuses one extraction, and
        # the identical arrays downstream hit the device-upload and
        # incidence caches of ``scenario_device``.
        alt_routes = alt_hops = None
        if self.n_paths > 1:
            alt_routes, alt_hops = fab.flow_route_set(
                pairs, self.n_paths, seed=self.route_seed)
            routes = alt_routes[:, 0].copy()
        else:
            routes = fab.flow_routes(pairs)
        F = len(pairs)
        hops = route_hops(routes)
        # CNP feedback delay ~ 2 * hops * (prop + serialisation) + NIC
        # turnaround; quantised to dt steps, >= 2 so the loop is never
        # same-step.
        per_hop = cfg.link.propagation_delay + cfg.link.mtu / cfg.link.line_rate
        rtt = 2 * hops * per_hop + 1e-6
        rtt_steps = np.maximum(2, np.round(rtt / cfg.sim.dt)).astype(np.int32)
        rate = cfg.link.line_rate if self.gen_rate is None else self.gen_rate
        # per-flow rates: workloads are built before the config's line
        # rate is known, so inf means "line rate" and a negative entry
        # -f means "fraction f of line rate".
        rates = self._per_flow(self.flow_rate, rate, F).astype(np.float64)
        rates = np.where(np.isfinite(rates), rates, cfg.link.line_rate)
        rates = np.where(rates < 0, -rates * cfg.link.line_rate,
                         rates).astype(np.float32)
        # scalar stays scalar (host-side API compat); per-flow goes [F]
        nic = (self._per_flow(self.flow_nic_buffer, 0.0, F)
               if self.flow_nic_buffer else self.nic_buffer)
        # virtual channels: only materialised when the config runs more
        # than one, so single-VC scenarios stay byte-identical to the
        # pre-VC builds (vc=None, victim still carried for metrics)
        vc = None
        n_vcs = int(getattr(cfg.link, "n_vcs", 1))
        if n_vcs > 1:
            from .routing import assign_vc
            alt = alt_routes if alt_routes is not None \
                else routes[:, None, :]
            fv = np.asarray(self.flow_vc, np.int32) \
                if self.flow_vc else None
            vc = assign_vc(alt, n_vcs, mode=self.vc_mode, flow_vc=fv)
        victim = None
        if self.flow_victim:
            victim = self._per_flow(
                tuple(bool(v) for v in self.flow_victim), False, F,
                dtype=bool)
        elif self.kind == "incast" and self.victim is not None:
            victim = np.zeros((F,), bool)
            victim[-1] = True          # the appended victim pair
        return Scenario(
            routes=routes,
            hops=hops,
            gen_rate=rates,
            t_start=self._per_flow(self.flow_t_start, self.t_start, F),
            t_stop=self._per_flow(self.flow_t_stop, self.t_stop, F),
            volume=self._per_flow(self.flow_volume, self.volume, F),
            capacity=topo.link_capacity.astype(np.float32),
            sink_switch=topo.sink_switch(),
            n_switches=topo.n_switches,
            # feedback delay is pinned to the minimal path's RTT even for
            # multi-path scenarios: the delay line is per-flow static, and
            # a mode-dependent RTT would make routing="min" on a K-path
            # scenario diverge from the K=1 build of the same workload.
            rtt_steps=rtt_steps,
            nic_buffer=nic,
            alt_routes=alt_routes,
            alt_hops=alt_hops,
            vc=vc,
            victim=victim,
        )


# ---------------------------------------------------------------------------
# padding + stacking
# ---------------------------------------------------------------------------


def pad_scenario(scn: Scenario, n_flows: int, n_hops: int,
                 n_links: int, n_paths: int | None = None) -> Scenario:
    """Grow a scenario to [n_flows, n_hops] flows and n_links links.

    PAD flows never generate (t_start = inf, zero rate/volume) and cross
    no links; PAD links carry no flow and a nominal capacity — both are
    inert in every scatter/reduce of the step, so padding cannot change
    delivered bytes (property-tested in test_experiments).

    ``n_paths`` pads the candidate axis of multi-path scenarios; padded
    candidate slots are all-PAD with hop count 0, which the selection
    logic reads as "no such detour" (``n_alt`` counts real slots only).
    ``None`` keeps the scenario's own K (single-path stays single-path).
    """
    F, H = scn.routes.shape
    L = scn.capacity.shape[0]
    K = 1 if scn.alt_routes is None else scn.alt_routes.shape[1]
    n_paths = K if n_paths is None else n_paths
    if n_flows < F or n_hops < H or n_links < L or n_paths < K:
        raise ValueError(f"pad target ({n_flows},{n_hops},{n_links},"
                         f"{n_paths}) smaller than scenario "
                         f"({F},{H},{L},{K})")

    def pad_f(x, fill):
        return np.concatenate(
            [x, np.full((n_flows - F,) + x.shape[1:], fill, x.dtype)])

    routes = np.full((n_flows, n_hops), PAD, np.int32)
    routes[:F, :H] = scn.routes
    alt_routes = alt_hops = None
    if not (n_paths == 1 and scn.alt_routes is None):
        alt_routes = np.full((n_flows, n_paths, n_hops), PAD, np.int32)
        alt_hops = np.zeros((n_flows, n_paths), np.int32)
        if scn.alt_routes is None:
            alt_routes[:F, 0, :H] = scn.routes
            alt_hops[:F, 0] = scn.hops
        else:
            alt_routes[:F, :K, :H] = scn.alt_routes
            alt_hops[:F, :K] = scn.alt_hops
    # VC padding: PAD flows/slots ride VC 0 (forced, so the incidence
    # scratch mapping stays exact); victim padding is non-victim.
    vc = None
    if scn.vc is not None:
        Kv = scn.vc.shape[1]
        Kp = n_paths if alt_routes is not None else Kv
        vc = np.zeros((n_flows, Kp, n_hops), np.int32)
        vc[:F, :Kv, :H] = scn.vc
    victim = None if scn.victim is None \
        else pad_f(np.asarray(scn.victim, bool), False)
    return Scenario(
        routes=routes,
        hops=pad_f(scn.hops, 0),
        gen_rate=pad_f(scn.gen_rate, 0.0),
        t_start=pad_f(scn.t_start, np.inf),
        t_stop=pad_f(scn.t_stop, np.inf),
        volume=pad_f(scn.volume, 0.0),
        capacity=np.concatenate(
            [scn.capacity, np.full((n_links - L,), 1.0, np.float32)]),
        sink_switch=np.concatenate(
            [scn.sink_switch, np.full((n_links - L,), -1, np.int32)]),
        n_switches=scn.n_switches,
        rtt_steps=pad_f(scn.rtt_steps, 2),
        # per-flow buffers pad with inf (PAD flows never generate);
        # scalar buffers broadcast on device, so they pass through
        nic_buffer=pad_f(np.asarray(scn.nic_buffer, np.float32), np.inf)
        if np.ndim(scn.nic_buffer) else scn.nic_buffer,
        alt_routes=alt_routes,
        alt_hops=alt_hops,
        vc=vc,
        victim=victim,
    )


def stack_scenarios(scns: Sequence[Scenario], n_vcs: int = 1):
    """Pad to common shape and stack into one batched ScenarioDev.

    Returns (batched ScenarioDev with leading run axis, padded host
    scenarios, n_switches_max).  ``n_vcs`` must match the sweep's
    shared ``LinkParams.n_vcs`` (the batch shares one incidence
    layout, so one static VC count).
    """
    F = max(s.routes.shape[0] for s in scns)
    H = max(s.routes.shape[1] for s in scns)
    L = max(s.capacity.shape[0] for s in scns)
    K = max(1 if s.alt_routes is None else s.alt_routes.shape[1]
            for s in scns)
    n_sw = max(s.n_switches for s in scns)
    padded = [pad_scenario(s, F, H, L, n_paths=K) for s in scns]
    devs = [scenario_device(s, n_vcs=n_vcs) for s in padded]
    batched = jax.tree.map(lambda *xs: jnp.stack(xs), *devs)
    return batched, padded, n_sw


def batch_dense_rows(padded: Sequence[Scenario], n_vcs: int,
                     reduce: str = "fused",
                     dense_rows: int | None = None) -> int:
    """The longest contributor list (or pinned row count) one batch of
    padded scenarios runs its dense reduction with (``dense_engine``).

    The row count must cover every run in the batch; any
    over-skew scenario disables the dense engine for the batch (0 = the
    segment-sum path, bit-identical), and the batch-wide max is
    re-clamped so one skewed run can't force the rest onto an oversized
    table.  An explicit ``dense_rows`` that cannot cover the batch also
    falls back to 0.  Shared by ``Sweep.run`` and the fleet planner so
    every shard pinned to the plan's value runs one program, bitwise
    the full batch's results.
    """
    if reduce != "fused":
        return 0
    if dense_rows is None:
        mls = [dense_reduce_rows(s, n_vcs) for s in padded]
        if 0 in mls:
            return 0
        s0 = padded[0]
        K = 1 if s0.alt_routes is None else s0.alt_routes.shape[1]
        return clamp_dense_rows(
            max(mls), s0.capacity.shape[0] * n_vcs,
            s0.routes.shape[0] * K * s0.routes.shape[1])
    if dense_rows > 0 and any(
            not 0 < dense_reduce_rows(s, n_vcs) <= dense_rows
            for s in padded):
        return 0                     # can't cover the batch: safe path
    return int(dense_rows)


# ---------------------------------------------------------------------------
# Sweep — N points, one jitted vmap-of-scan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    name: str
    cfg: CCConfig
    scenario: Scenario            # built tensors (specs compile on add)


def _replace_path(cfg: CCConfig, path: str, value) -> CCConfig:
    """dataclasses.replace through dotted paths, e.g. "dcqcn.kmin"."""
    head, _, rest = path.partition(".")
    if not rest:
        return dataclasses.replace(cfg, **{head: value})
    sub = getattr(cfg, head)
    return dataclasses.replace(
        cfg, **{head: _replace_path(sub, rest, value)})


def config_grid(cfg: CCConfig, **axes) -> dict[str, CCConfig]:
    """{"kmin=8192": cfg', ...} over the product of dotted-path axes.

    ``config_grid(cfg, **{"dcqcn.kmin": [8e3, 15e3], "rev.erp_rai": [...]})``
    """
    out = {"": cfg}
    for path, values in axes.items():
        leaf = path.rsplit(".", 1)[-1]
        nxt = {}
        for name, c in out.items():
            for v in values:
                key = f"{leaf}={v:g}" if isinstance(v, (int, float)) else \
                    f"{leaf}={v}"
                nxt[f"{name}/{key}" if name else key] = \
                    _replace_path(c, path, v)
        out = nxt
    return out


#: The sweep-executable cache: every ``Sweep.run`` resolves its compiled
#: program here, keyed by the full structural signature (static scan
#: configuration + input pytree treedef + leaf shapes/dtypes).  It is a
#: module-level singleton on purpose — the what-if serving engine
#: (``repro.serve.whatif``) snapshots its :class:`CacheStats` to report
#: hit rates and to *assert* "this query replay compiled exactly once".
SWEEP_EXEC_CACHE = ExecutableCache(capacity=32, name="sweep")


def _sweep_scan_fn(n_samples: int, trace_every: int, dt: float,
                   n_switches: int, reduce: str, dense_blocks: tuple,
                   use_kernels: "bool | str", interpret: bool,
                   n_vcs: int, substep_block: int, mesh):
    """Build the (unjitted) sweep scan for one static configuration.

    The whole sweep is one vmap-of-(decimating)-scan.  With ``mesh`` the
    run axis is sharded over every mesh axis via ``shard_map`` — each
    device advances (and decimates the traces of) its own slice of the
    run batch, with zero cross-device communication, so a sharded sweep
    is bitwise the single-device sweep cut into ``mesh.size`` pieces.

    ``substep_block`` is the megakernel's in-kernel scan depth (0 on the
    non-mega tiers): with ``use_kernels="mega"`` the inner per-step scan
    is replaced by one vmapped whole-window ``megastep_block`` launch
    per trace sample, ``substep_block`` (= ``trace_every``) substeps
    deep, the fluid state staying kernel-resident throughout.
    """
    tier = kernel_tier(use_kernels)
    if tier == "mega":
        body = step_body_fn(dt=dt, n_switches=n_switches, reduce=reduce,
                            dense_blocks=dense_blocks, n_vcs=n_vcs)
        from repro.kernels.fluid_step import megastep_block

        def scan_fn(st_b, sd_b, par_b):
            def block(st):
                return jax.vmap(
                    lambda s, sd, par: megastep_block(
                        s, sd, par, body=body,
                        n_substeps=substep_block,
                        acc_init=_zero_accum, acc_update=_acc_update,
                        make_sample=_window_sample, n_vcs=n_vcs, dt=dt,
                        interpret=interpret)
                )(st, sd_b, par_b)

            return decimating_scan(None, st_b, n_samples, trace_every,
                                   dt, n_vcs, block_fn=block)
    else:
        def scan_fn(st_b, sd_b, par_b):
            # flow tier: hoist the reaction kernels' SMEM param rows out
            # of the scan — packed once per trace, reused every substep
            # (None on the other tiers: an empty pytree vmaps freely).
            packed_b = jax.vmap(
                lambda par: cc.pack_react_rows(
                    par.react, par.line_rate, jnp.float32(dt))
            )(par_b) if tier == "flow" else None

            def step(st):
                return jax.vmap(
                    lambda s, sd, par, pk: fluid_step(
                        s, sd, par, dt=dt, n_switches=n_switches,
                        reduce=reduce, dense_blocks=dense_blocks,
                        use_kernels=use_kernels, interpret=interpret,
                        n_vcs=n_vcs, packed_react=pk)
                )(st, sd_b, par_b, packed_b)

            return decimating_scan(step, st_b, n_samples, trace_every,
                                   dt, n_vcs)

    if mesh is None:
        return scan_fn
    from jax.sharding import PartitionSpec as P
    run_spec = P(tuple(mesh.axis_names))     # leading run axis sharded
    return jax.shard_map(
        scan_fn, mesh=mesh,
        in_specs=(run_spec, run_spec, run_spec),
        # decimating_scan returns (final [R, ...], traces [T, R, ...])
        out_specs=(run_spec, P(None, *run_spec)),
        check_vma=False)


@obs.span("repro.sweep.resolve")
def _sweep_executable(static: tuple, args: tuple):
    """Resolve one sweep launch to a cached compiled executable.

    The cache key is the *structural signature*: the static scan
    configuration plus the input pytree's treedef and every leaf's
    shape/dtype — exactly what determines the compiled program, so a
    cache hit swaps traced data into an existing executable and a miss
    is a real compile (counted once, in ``SWEEP_EXEC_CACHE`` stats).
    Single-device launches are AOT-lowered (``jit(...).lower(args)
    .compile()``) so compile time lands in the cache's ``build_s``
    instead of smearing into the first run; the mesh-sharded path keeps
    the jitted callable (shard_map AOT is not worth the API risk here —
    serving never passes a mesh).
    """
    mesh = static[-1]

    def build():
        fn = jax.jit(_sweep_scan_fn(*static))
        if mesh is not None:
            return fn
        return fn.lower(*args).compile()

    return SWEEP_EXEC_CACHE.get_or_build(
        structural_signature(static, args), build)


class Sweep:
    """A batch of (config, scenario) points run as one device launch.

    Points come in as ``(name, cfg, scenario-or-spec)`` triples; specs
    are compiled against their point's config.  All points must agree on
    ``sim.dt`` and ``sim.trace_every`` (they share the scan); shapes are
    padded to the batch maximum.
    """

    def __init__(self, points: Sequence[tuple[str, "CCConfig | CCSpec",
                                              "ScenarioSpec | Scenario"]]):
        if not points:
            raise ValueError("empty sweep")
        self.points: list[SweepPoint] = []
        names = set()
        for name, cfg, scn in points:
            if name in names:
                raise ValueError(f"duplicate sweep point name: {name!r}")
            names.add(name)
            if isinstance(scn, ScenarioSpec):
                scn = scn.build(cfg)
            check_routing_paths(cfg, scn)
            self.points.append(SweepPoint(name, cfg, scn))
        dts = {p.cfg.sim.dt for p in self.points}
        kps = {p.cfg.sim.trace_every for p in self.points}
        if len(dts) > 1 or len(kps) > 1:
            raise ValueError(
                f"sweep points disagree on sim.dt ({dts}) or "
                f"trace_every ({kps}); they share one scan")
        vcs = {int(getattr(p.cfg.link, "n_vcs", 1)) for p in self.points}
        if len(vcs) > 1:
            raise ValueError(
                f"sweep points disagree on link.n_vcs ({sorted(vcs)}); "
                f"the VC count is a static shape parameter shared by "
                f"the whole batch — run them as separate sweeps")
        self.n_vcs = vcs.pop()

    @classmethod
    def grid(cls, configs, scenarios) -> "Sweep":
        """Cross named configs with named scenarios/specs.

        ``configs``: dict[str, CCConfig | CCSpec] (or one config);
        ``scenarios``: dict[str, ScenarioSpec | Scenario] (or one).
        Point names are "cfg/scenario" (or the sole non-dict's name).
        """
        if isinstance(configs, (CCConfig, CCSpec)):
            configs = {"": configs}
        if isinstance(scenarios, (ScenarioSpec, Scenario)):
            scenarios = {getattr(scenarios, "name", "scenario"): scenarios}
        points = []
        for cn, cfg in configs.items():
            for sn, scn in scenarios.items():
                name = f"{cn}/{sn}" if cn and sn else (cn or sn)
                points.append((name, cfg, scn))
        return cls(points)

    @property
    def names(self) -> list[str]:
        return [p.name for p in self.points]

    def subset(self, keys: Sequence["str | int"]) -> "Sweep":
        """A new Sweep over the named (or indexed) points — the grid
        slicing primitive behind shard-addressable fleet execution.
        Scenarios pass through as built tensors; order follows ``keys``.
        """
        names = self.names
        pts = []
        for key in keys:
            r = key if isinstance(key, int) else names.index(key)
            p = self.points[r]
            pts.append((p.name, p.cfg, p.scenario))
        return Sweep(pts)

    @obs.span("repro.sweep.stage")
    def _prepare(self, n_steps: int | None = None,
                 trace_every: int | None = None, *, mesh=None,
                 reduce: str = "fused", use_kernels: bool = False,
                 interpret: bool = False, pad_runs_to: int | None = None,
                 min_delay_slots: int | None = None,
                 dense_rows: int | None = None,
                 temperature: float = 0.0,
                 min_switches: int | None = None):
        """Stack, pad and stage the batch; returns
        ``(static, (st_b, sd_b, par_b), n_samples, k)`` — everything a
        launch needs short of resolving the executable.  Shared by
        :meth:`run` and the fleet's streaming runner
        (``repro.fleet.stream``), which swaps the scan depth in
        ``static`` for per-window execution but must otherwise stage
        the bit-identical program.
        """
        if temperature and use_kernels:
            raise ValueError(
                "temperature > 0 needs use_kernels=False: the Pallas "
                "kernel tiers implement the hard dynamics only")
        cfg0 = self.points[0].cfg
        n_samples, k = _resolve_steps(cfg0, n_steps, trace_every)
        scns = [p.scenario for p in self.points]
        sd_b, padded, n_sw = stack_scenarios(scns, n_vcs=self.n_vcs)
        dense_blocks, layout = dense_engine(
            padded, self.n_vcs,
            batch_dense_rows(padded, self.n_vcs, reduce, dense_rows),
            pinned=dense_rows is not None,
            mega=kernel_tier(use_kernels) == "mega")
        if layout is not None:
            red_idx, red_back, n_rows = layout
            sd_b = sd_b._replace(red_idx=red_idx, red_back=red_back)
            obs.count("sweep.reduce_slots", red_idx.size)
            obs.count("sweep.reduce_rows", n_rows)
        if min_switches is not None:
            n_sw = max(n_sw, int(min_switches))
        D = max(delay_depth(s) for s in padded)
        if min_delay_slots is not None:
            D = max(D, int(min_delay_slots))
        st_b = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[init_state(s, p.cfg, delay_slots=D)
              for s, p in zip(padded, self.points)])
        par_b = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[step_params(p.cfg, temperature=temperature)
              for p in self.points])
        R = len(self.points)
        R_target = R if pad_runs_to is None else max(R, int(pad_runs_to))
        if mesh is not None and R_target % mesh.size:
            R_target += mesh.size - R_target % mesh.size
        if R_target > R:
            pad_r = R_target - R                 # replicate the last run
            rep = lambda x: jnp.concatenate(
                [x] + [x[-1:]] * pad_r, axis=0)
            st_b, sd_b, par_b = (jax.tree.map(rep, t)
                                 for t in (st_b, sd_b, par_b))
        # the substep-block depth (the megakernel's in-kernel scan
        # length) is part of the executable signature: a mega sweep
        # re-blocked at a different trace_every is a different program
        substep_block = k if kernel_tier(use_kernels) == "mega" else 0
        static = (n_samples, k, float(cfg0.sim.dt), n_sw, reduce,
                  dense_blocks, use_kernels, interpret, self.n_vcs,
                  substep_block, mesh)
        return static, (st_b, sd_b, par_b), n_samples, k

    def run(self, n_steps: int | None = None,
            trace_every: int | None = None, *, mesh=None,
            reduce: str = "fused", use_kernels: bool = False,
            interpret: bool = False, pad_runs_to: int | None = None,
            min_delay_slots: int | None = None,
            dense_rows: int | None = None,
            temperature: float = 0.0,
            min_switches: int | None = None) -> "SweepResult":
        """Execute all points as one device launch.

        ``mesh``: a ``jax.sharding.Mesh`` (e.g. ``repro.dist.sweep_mesh()``)
        shards the run axis across its devices with ``shard_map``; the
        batch is padded to a multiple of ``mesh.size`` by replicating
        the last point (padding runs are discarded on return) and each
        shard decimates its own traces.  Results are bitwise identical
        to the single-device launch, run for run.

        ``reduce`` / ``use_kernels`` / ``interpret`` select the per-step
        reduction engine and the Pallas tier (see ``fluid_step``);
        ``use_kernels="mega"`` runs each trace window as one whole-step
        megakernel launch per run, ``trace_every`` substeps deep.

        The remaining knobs exist for serving (``repro.serve.whatif``),
        which must keep the executable-cache key stable across batches
        of varying composition; results are bitwise unaffected:
          * ``pad_runs_to`` grows the run axis to a fixed width by
            replicating the last point (discarded on return) — the
            micro-batcher's pad-to-bucket on the vmap axis;
          * ``min_delay_slots`` floors the delay-line depth (normally
            sized from the batch's worst RTT, which varies with batch
            mix; extra slots are inert by construction);
          * ``dense_rows`` pins the dense reduction to the rectangle
            of every queue x that many positions, whose shape does not
            depend on the batch's content (``None`` = the jagged layout
            derived from the batch; a value that cannot cover the
            batch's skew falls back to 0, the segment-sum path; all are
            bit-identical).

        ``temperature`` > 0 runs the soft-relaxed dynamics
        (``repro.tune.soft``) — smoothed marking/PFC/notification
        gates for differentiable tuning.  The default 0 is the exact
        hard model (bitwise; temperature is traced data, so both share
        one compiled executable).  Soft runs require
        ``use_kernels=False`` (the Pallas per-flow kernels implement
        the hard path only).

        ``min_switches`` floors the static switch count the scan is
        built for (normally the batch max) — the fleet planner pins it
        so every shard of a grid compiles and runs the exact program
        the full batch would; extra switch rows are inert.
        """
        static, args, n_samples, k = self._prepare(
            n_steps, trace_every, mesh=mesh, reduce=reduce,
            use_kernels=use_kernels, interpret=interpret,
            pad_runs_to=pad_runs_to, min_delay_slots=min_delay_slots,
            dense_rows=dense_rows, temperature=temperature,
            min_switches=min_switches)
        st_b, sd_b, par_b = args
        R = len(self.points)
        exec_fn = _sweep_executable(static, args)
        with obs.span("repro.sweep.execute"):
            final, tr = jax.block_until_ready(exec_fn(st_b, sd_b, par_b))
        with obs.span("repro.sweep.fetch"):
            tr, final = jax.device_get((tr, final))
            obs.count("sweep.fetch_bytes", sum(
                x.nbytes for x in jax.tree.leaves((tr, final))))
        times = (np.arange(n_samples) + 1) * k * self.points[0].cfg.sim.dt
        # scan stacks samples on axis 0 -> [T, R, ...]; runs lead on host
        return SweepResult(
            points=self.points, times=times,
            traces=jax.tree.map(lambda x: np.moveaxis(x, 0, 1)[:R], tr),
            final=jax.tree.map(lambda x: x[:R], final),
            trace_every=k)


def trim_final(fin: FluidState, F: int) -> FluidState:
    """An (unbatched) final state trimmed back to its true flow count —
    the inverse of ``pad_scenario`` for result views (PAD flows are
    inert, so trimming loses nothing).  Used by the sweep's per-point
    views and by the what-if engine's bucket-padded query slicing."""
    flow = lambda x: x[:F]
    return FluidState(
        qh=flow(fin.qh), nicq=flow(fin.nicq), delivered=flow(fin.delivered),
        offered=flow(fin.offered), dropped=flow(fin.dropped),
        est=flow(fin.est), paused=fin.paused, rate=flow(fin.rate),
        rp_target=flow(fin.rp_target), alpha=flow(fin.alpha),
        byte_cnt=flow(fin.byte_cnt), tmr=flow(fin.tmr),
        alpha_tmr=flow(fin.alpha_tmr), bc_stage=flow(fin.bc_stage),
        t_stage=flow(fin.t_stage), hold=flow(fin.hold),
        np_tmr=flow(fin.np_tmr), trig_buf=fin.trig_buf[:, :F],
        tgt_buf=fin.tgt_buf[:, :F], path_idx=flow(fin.path_idx),
        cc={k: flow(v) for k, v in fin.cc.items()},
        t=fin.t)


def _slice_final(fin: FluidState, r: int, F: int) -> FluidState:
    """Run r's final state, trimmed back to its true flow count."""
    return trim_final(jax.tree.map(lambda x: x[r], fin), F)


@dataclasses.dataclass
class SweepResult:
    """All runs' decimated traces, indexable by point name (or index)
    into per-point ``SimResult`` views trimmed to their true flows."""

    points: list[SweepPoint]
    times: np.ndarray              # [T] window-end seconds
    traces: object                 # TraceSample of [R, T, ...] numpy
    final: object                  # FluidState with leading [R]
    trace_every: int

    @property
    def names(self) -> list[str]:
        return [p.name for p in self.points]

    def __len__(self) -> int:
        return len(self.points)

    def __contains__(self, name: str) -> bool:
        return name in self.names

    def __getitem__(self, key: "str | int") -> SimResult:
        if isinstance(key, int):
            r = key
        elif key in self.names:
            r = self.names.index(key)
        else:
            raise KeyError(f"{key!r} not in sweep; points: {self.names}")
        p = self.points[r]
        F = p.scenario.routes.shape[0]
        tr = self.traces
        return SimResult(
            cfg=p.cfg, scn=p.scenario, times=self.times,
            delivered=tr.delivered[r][:, :F],
            rate=tr.rate[r][:, :F],
            inst_thr=tr.inst_thr[r][:, :F],
            max_q=tr.max_q[r], n_paused=tr.n_paused[r],
            marked=tr.marked[r][:, :F], cnp=tr.cnp[r][:, :F],
            n_nonmin=tr.n_nonmin[r],
            final=_slice_final(self.final, r, F),
            ctrl=tr.ctrl[r][:, :F],
            trace_every=self.trace_every,
            pause_time=None if tr.pause_time is None
            else tr.pause_time[r],
            vc_stall=None if tr.vc_stall is None else tr.vc_stall[r])

    def items(self):
        for i, p in enumerate(self.points):
            yield p.name, self[i]

    def to_dict(self, *, traces: bool = True) -> dict:
        """JSON-ready dict (numpy-free scalars, tagged arrays); the
        full form round-trips bit-exactly via :meth:`from_dict` — per
        point views of the reconstruction match the original's (see
        ``repro.core.serialize``)."""
        from .serialize import sweepresult_to_dict
        return sweepresult_to_dict(self, traces=traces)

    @classmethod
    def from_dict(cls, d: dict) -> "SweepResult":
        from .serialize import sweepresult_from_dict
        return sweepresult_from_dict(d)

    def summary(self) -> dict[str, dict]:
        """Headline numbers per point (the Fig. 2/3 table in one dict)."""
        return {name: res.summary() for name, res in self.items()}
