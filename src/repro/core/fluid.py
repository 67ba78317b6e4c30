"""Dense fluid model of the CC closed loop (PFC / DCQCN / DCQCN-Rev).

TPU-native adaptation of the paper's event-driven evaluation (DESIGN.md §2):
the whole network is a fixed-shape state advanced by one fused, branch-free
update per ``dt``.  No event queue exists; flows x hops are vectorised.

Representation (compact, scales to DC-size):
  * ``routes[F, H]`` — link id crossed at each hop (PAD = -1).  H is
                       whatever the fabric's route table needs (6 for
                       the 3-stage CLOS, 2h for an h-level XGFT, 5 for
                       dragonfly — see ``repro.net``); every update
                       below is shape-polymorphic in it, and mixed
                       fabrics pad to a common H when stacked.
  * ``qh[F, H]``     — bytes of flow f queued at the *sink* of wire h
                       (the input buffer of the downstream switch), waiting
                       to cross wire h+1.  The last wire delivers to the
                       host, so qh[:, hops-1] is always 0.
  * ``nicq[F]``      — host backlog (generated, not yet injected).

Adaptive routing: scenarios may carry K candidate paths per flow
(``alt_routes[F, K, H]``, slot 0 minimal, slots 1..K-1 Valiant detours
— see ``repro.net.routing.RouteSet``); ``FluidState.path_idx`` names
each flow's live candidate and ``StepParams.route_code`` the policy
(0 = min, 1 = valiant, 2 = ugal).  Selection happens at the top of the
step, at flow start and (UGAL) on CNP-arrival epochs: UGAL-L compares
queue-occupancy-weighted hops of the minimal path against one sampled
detour, built from the per-link backlog the model already tracks, with
ties keeping the minimal route.  Switching a flow mid-flight
reinterprets its queued bytes onto the new path's hop positions — the
usual fluid-model abstraction (bytes are a continuum, not packets).

Per step (Jacobi, from pre-step state):
  0. path selection (min / valiant / ugal) at epoch flows;
  1. generation into nicq (rate-limited window generator, finite NIC buf);
  2. transfers: every wire w serves the queues feeding it proportionally
     to their backlog, capped by C_w*dt, gated by PFC pause, and scaled by
     a strict-FIFO HoL factor (a queue whose head bytes belong to a paused
     flow stalls everyone — the paper's victim pathology);
  3. PFC: a wire pauses when its sink queue crosses XOFF (hysteresis XON),
     plus a shared-pool pause per switch;
  4. marking: one registered ``repro.core.cc.MARKING`` stage — CP
     (occupancy only), ECP (occupancy AND flow rate above its
     waterfilled fair grant on its next wire — victims never marked),
     slope (RED-style kmin..kmax ramp, error-diffused), ...;
  5. notification: one ``cc.NOTIFICATION`` stage — NP (50us
     suppression), ENP (fast coalescing + severity payload = fair
     grant at the marking queue), FNCC (in-path: the marking hop
     writes the return path, shrinking the feedback delay);
  6. reaction: one ``cc.REACTION`` stage — fixed-rate PFC source, RP
     (DCQCN alpha/stage machine), ERP (set to signalled fair share,
     hold, desynchronised additive recovery), swift (delay-target).

All arrays are float32; the update is pure jnp and runs inside lax.scan.
Each numbered phase runs under its own ``obs.scope`` (``fluid.select``,
``fluid.generate``, ``fluid.transfer``, ``fluid.pfc``, ``fluid.mark``,
``fluid.notify``, ``fluid.react``), the per-link reductions under
``fluid.reduce`` inside them and the step's trace under
``fluid.decimate``: names in the compiled program's op metadata, so a
profiler trace's device time splits by phase.  They add no op.

Layering (the Sweep engine in ``experiments.py`` builds on this):
  * ``Scenario``        — host-side numpy tensors describing one workload.
  * ``ScenarioDev``     — the same tensors as device arrays, the exact
                          pytree ``fluid_step`` consumes.  Batched sweeps
                          stack R of these and ``vmap`` over the leading
                          axis.
  * ``StepParams``      — every config scalar the update reads, as
                          traced values (NOT python statics), so one
                          compiled step serves all stage combinations /
                          param grids.
  * ``fluid_step``      — the pure per-``dt`` update.  Stage selection
                          (``mark_code`` / ``notif_code`` /
                          ``react_code``, see ``repro.core.cc``) happens
                          with ``jnp.where`` on traced selectors, which
                          is what lets a stage ablation ride one jit.
"""

from __future__ import annotations

import collections
import functools
import hashlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import cc, obs
from .params import CCConfig, CCSpec, ROUTING_MODES
from .routing import PAD, link_incidence
from repro.tune import soft


class Scenario(NamedTuple):
    """Static per-run tensors (host numpy; moved to device once)."""

    routes: np.ndarray        # [F, H] int32 link ids, PAD = -1
    hops: np.ndarray          # [F] int32
    gen_rate: np.ndarray      # [F] f32 B/s offered by the generator
    t_start: np.ndarray       # [F] f32 s
    t_stop: np.ndarray        # [F] f32 s (generator closes)
    volume: np.ndarray        # [F] f32 B total work (inf = window-limited)
    capacity: np.ndarray      # [L] f32 B/s per directed link
    sink_switch: np.ndarray   # [L] int32 (-1 for host sinks)
    n_switches: int
    rtt_steps: np.ndarray     # [F] int32 CNP feedback delay in dt steps
    # B of host NIC queue: a scalar (shared) or a per-flow [F] array —
    # mixed workloads give deep buffers to volume-mode collective flows
    # and shallow ones to window-mode background traffic.
    nic_buffer: "float | np.ndarray" = 4e6
    # multi-path candidates (adaptive routing): K per-flow paths, slot 0
    # the minimal route (== ``routes``), slots 1..K-1 Valiant detours.
    # None = single-path scenario (selection is a no-op).
    alt_routes: "np.ndarray | None" = None    # [F, K, H] int32, PAD-padded
    alt_hops: "np.ndarray | None" = None      # [F, K] int32 (0 = no path)
    # static virtual-channel assignment per candidate hop (values in
    # [0, n_vcs); see ``repro.core.routing.assign_vc``).  None = all
    # VC 0.  Only read when the config's ``LinkParams.n_vcs > 1``;
    # under n_vcs = 1 every VC collapses onto the single wire queue.
    vc: "np.ndarray | None" = None            # [F, K, H] int32
    # victim-flow mask for the PFC-pathology metrics: flows that do NOT
    # contribute to the congestion under test but share fabric with it
    # (``SimResult.victim_slowdown`` aggregates over these).  None = no
    # designated victims.
    victim: "np.ndarray | None" = None        # [F] bool


class ScenarioDev(NamedTuple):
    """Device-side scenario: the pytree ``fluid_step`` consumes.

    A batched sweep stacks R of these along a new leading axis and vmaps;
    every field is data, so runs with different routes / rates / RTTs
    share one compiled step.  Routes live only in the candidate stack
    ``alt_routes`` (single-path scenarios mirror into K = 1) — the
    host-side ``Scenario.routes``/``hops`` stay the minimal slot 0.
    """

    gen_rate: jnp.ndarray     # [F] f32
    t_start: jnp.ndarray      # [F] f32
    t_stop: jnp.ndarray       # [F] f32
    volume: jnp.ndarray       # [F] f32
    cap_ext: jnp.ndarray      # [L+1] f32 (scratch slot L for PAD scatters)
    sink_ext: jnp.ndarray     # [L+1] int32
    rtt: jnp.ndarray          # [F] int32
    nic_buffer: jnp.ndarray   # [F] f32 (host scalars broadcast per flow)
    alt_routes: jnp.ndarray   # [F, K, H] int32 (K = 1 mirrors ``routes``)
    alt_hops: jnp.ndarray     # [F, K] int32
    # static VC per candidate hop (all-zero when the scenario has none);
    # only consulted by ``fluid_step(..., n_vcs > 1)`` — under one VC
    # the queue index is the wire index and this tensor is dead data.
    vc: jnp.ndarray           # [F, K, H] int32 in [0, n_vcs)
    # per-flow ERP recovery jitter (Weyl sequence), hoisted here so the
    # step never rebuilds host constants inside a trace
    jitter: jnp.ndarray       # [F] f32
    # fused-reduction incidence (see core.routing.link_incidence): the
    # flattened [F*K*H] candidate entries stably sorted by link id.
    # Every per-link scatter-add of the step becomes one gather by
    # ``red_perm`` + sorted multi-channel segment sum over ``red_seg``;
    # ``red_off`` are the CSR offsets (each link's span of the order).
    red_perm: jnp.ndarray     # [F*K*H] int32
    red_seg: jnp.ndarray      # [F*K*H] int32
    red_off: jnp.ndarray      # [L+2] int32
    # same trick for the per-switch shared-pool reduction: link ids
    # stably sorted by sink switch (host sinks -> scratch segment)
    pool_perm: jnp.ndarray    # [L] int32
    pool_seg: jnp.ndarray     # [L] int32
    # the dense reduction's jagged layout (``dense_layout``, attached
    # per batch): each slot's row of the flattened channels, and each
    # queue's rank among the layout's rows.  None on the other engines.
    red_idx: "jnp.ndarray | None" = None     # [T] int32
    red_back: "jnp.ndarray | None" = None    # [S + 1] int32


class StepParams(NamedTuple):
    """Per-run CC constants as traced scalars (stack + vmap for sweeps).

    Stage selection is data, not structure: ``mark_code`` /
    ``notif_code`` / ``react_code`` name one registered component per
    family in ``repro.core.cc`` (selected inside the step with
    ``jnp.where``, like ``route_code``), and ``mark`` / ``notif`` /
    ``react`` carry each family's param union as a flat dict pytree —
    so any (marking x notification x reaction x param grid) product
    shares ONE compiled step.
    """

    mark_code: jnp.ndarray    # [] int32 — cc.MARKING entry
    notif_code: jnp.ndarray   # [] int32 — cc.NOTIFICATION entry
    react_code: jnp.ndarray   # [] int32 — cc.REACTION entry
    route_code: jnp.ndarray   # [] int32  — 0 min / 1 valiant / 2 ugal
    line_rate: jnp.ndarray    # [] f32
    xoff: jnp.ndarray         # [] f32
    xon: jnp.ndarray          # [] f32
    pool_xoff: jnp.ndarray    # [] f32
    port_buffer: jnp.ndarray  # [] f32
    ecp_beta: jnp.ndarray     # [] f32 — crossing-rate EWMA gain (the
    #   demand estimate is shared step infrastructure, not a stage)
    mark: dict                # marking-family param union ([] scalars)
    notif: dict               # notification-family param union
    react: dict               # reaction-family param union
    # Soft-relaxation temperature (``repro.tune.soft``): 0 runs the
    # exact hard dynamics (bitwise — every softened site selects its
    # original expression); > 0 smooths the hard gates (PFC
    # hysteresis, marking thresholds, CNP windows, rate clamps) so
    # ``jax.grad`` flows through the dt-scan.  Traced data like every
    # other constant: hard sweeps and soft tuner rollouts share ONE
    # compiled step.
    temperature: jnp.ndarray  # [] f32


class FluidState(NamedTuple):
    qh: jnp.ndarray           # [F, H] bytes at hop queues
    nicq: jnp.ndarray         # [F]
    delivered: jnp.ndarray    # [F]
    offered: jnp.ndarray      # [F] bytes the generator admitted into nicq
    dropped: jnp.ndarray      # [F] generator overflow (app backpressure)
    est: jnp.ndarray          # [F, H] EWMA crossing rate per wire (B/s)
    # Pause level per (wire, VC) queue: exact 0/1 in hard mode
    # (temperature == 0), fractional under the soft PFC hysteresis —
    # float32 so the pause gate is a differentiable multiplier instead
    # of a boolean select.  Flat [L * n_vcs] layout (queue q of wire w
    # at w * n_vcs + q), so the single-VC model keeps its legacy [L]
    # shape bit-for-bit.
    paused: jnp.ndarray       # [L * n_vcs] f32
    # reaction-point state (DCQCN RP and ERP share slots where sensible)
    rate: jnp.ndarray         # [F] current injection rate
    rp_target: jnp.ndarray    # [F]
    alpha: jnp.ndarray        # [F]
    byte_cnt: jnp.ndarray     # [F]
    tmr: jnp.ndarray          # [F]
    alpha_tmr: jnp.ndarray    # [F]
    bc_stage: jnp.ndarray     # [F] int32
    t_stage: jnp.ndarray      # [F] int32
    hold: jnp.ndarray         # [F] ERP hold-down timer
    np_tmr: jnp.ndarray       # [F] time since last CNP emission
    trig_buf: jnp.ndarray     # [D, F] CNP in flight (delay line)
    tgt_buf: jnp.ndarray      # [D, F] severity payload in flight
    path_idx: jnp.ndarray     # [F] int32 selected candidate (0 = minimal)
    # per-stage state pytree: every registered cc stage contributes its
    # [F]-shaped keys (e.g. slope marking's error-diffusion accumulator,
    # swift's decrease-guard timer), so the structure is stable across a
    # whole sweep batch and unselected stages pass theirs through.
    cc: dict
    t: jnp.ndarray            # [] int32 step counter


class StepTrace(NamedTuple):
    delivered: jnp.ndarray    # [F] cumulative bytes
    rate: jnp.ndarray         # [F] RP rate
    inst_thr: jnp.ndarray     # [F] delivery rate this step (B/s)
    max_q: jnp.ndarray        # [] hottest queue (bytes)
    n_paused: jnp.ndarray     # [] paused wires
    marked: jnp.ndarray       # [F] marked this step?
    cnp: jnp.ndarray          # [F] CNP received this step?
    n_nonmin: jnp.ndarray     # [] flows currently on a non-minimal path
    # control-traffic counter: notification messages (CNP/ENP/FNCC)
    # emitted this step — exact 0/1 per flow in hard mode, fractional
    # emission intensity under the soft model.  Accumulated (not
    # sampled) by the decimating scan, it feeds the control-overhead
    # objective in repro.tune and SimResult.summary().
    ctrl: jnp.ndarray         # [F] f32 notifications emitted this step
    # PFC pathology instrumentation (accumulated, like ``ctrl``):
    # ``pause_time`` is wire-seconds of pause asserted this step
    # (sum over queues of pause level x dt); ``vc_stall`` splits the
    # same quantity per VC ([n_vcs], so [1] in the single-VC model) —
    # the per-lane stall budget a pause storm burns.
    pause_time: jnp.ndarray   # [] f32 wire-seconds paused this step
    vc_stall: jnp.ndarray     # [V] f32 per-VC wire-seconds paused


DELAY_SLOTS = 32              # legacy fixed delay-line depth (see below)


def delay_depth(scn: Scenario) -> int:
    """Delay-line depth covering every flow's CNP feedback delay.

    The legacy code used a hard ``DELAY_SLOTS = 32`` ring and silently
    wrapped ``rtt_steps % 32``, corrupting the control loop of any path
    with >= 32 steps of feedback delay.  The depth is now derived from
    the scenario; ``DELAY_SLOTS`` survives only as an explicit opt-in
    (and raises instead of wrapping).
    """
    return max(2, int(np.max(scn.rtt_steps)) + 1)


def _check_delay(scn: Scenario, delay_slots: int) -> int:
    max_rtt = int(np.max(scn.rtt_steps))
    if max_rtt >= delay_slots:
        raise ValueError(
            f"rtt_steps up to {max_rtt} overflow the {delay_slots}-slot "
            f"delay line; pass delay_slots >= {max_rtt + 1} (or None to "
            f"size it from the scenario)")
    return delay_slots


def _flow_jitter(n: int) -> np.ndarray:
    """Deterministic per-flow jitter in [-1, 1] (Weyl sequence)."""
    x = (np.arange(n, dtype=np.uint64) * np.uint64(2654435761)) % np.uint64(2**32)
    return (x.astype(np.float64) / 2**31 - 1.0).astype(np.float32)


def _split12(a: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``a == hi + lo`` exactly, ``hi`` the float32 ``a`` rounded to 12
    significant bits and ``|lo| <= ulp(hi) / 2`` (Veltkamp's split, made
    with integer ops so that no multiply-add can be contracted into it)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(
        (bits + jnp.uint32(0x800)) & jnp.uint32(0xFFFFF000), jnp.float32)
    return hi, a - hi


def round_quotient(q: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """``q``, a float32 quotient within a few ulps of ``x / y``, corrected
    to the float32 nearest ``x / y``: the residual ``x - q * y`` is formed
    exactly (Dekker's product), and ``q + residual / y`` rounds to the
    nearest float unless ``x / y`` lies within about 2**-21 ulp of a tie.
    An exact quotient (``x / x``) comes out exact.  Non-finite corrections
    (``y`` zero or infinite, overflow) keep ``q``."""
    p = q * y
    qh, ql = _split12(q)
    yh, yl = _split12(y)
    e = ((qh * yh - p) + qh * yl + ql * yh) + ql * yl      # q * y == p + e
    r = (x - p) - e
    q1 = q + r / y
    return jnp.where(jnp.isfinite(q1), q1, q)


def fdiv(x, y) -> jnp.ndarray:
    """``x / y`` in float32, rounded to nearest on every backend.

    The TPU divides as a refined reciprocal times ``x``: on a TPU v5e
    34.6% of random quotients are 1-2 ulps off, and ``x / x`` can read
    below 1 (a queue served its whole backlog keeps a residue), which is
    enough for a pause, mark or path to be decided the other way a few
    steps later.  One exact-residual correction (``round_quotient``)
    brings the quotient to what the CPU gives; elsewhere ``fdiv`` is
    ``x / y``, bit for bit."""
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    q = x / y
    return jax.lax.platform_dependent(
        q, x, y, tpu=round_quotient, default=lambda q, x, y: q)


@functools.lru_cache(maxsize=128)
def _index_consts(F: int, H: int) -> tuple[np.ndarray, np.ndarray]:
    """(arange_h [1, H], fidx [F]) — shared across traces of one shape."""
    return (np.arange(H, dtype=np.int32)[None, :],
            np.arange(F, dtype=np.int32))


def _digest(x: np.ndarray) -> tuple:
    x = np.ascontiguousarray(x)
    return (x.shape, x.dtype.str, hashlib.sha1(x.tobytes()).hexdigest())


def _memo_lru(cache: collections.OrderedDict, maxsize: int, key, fn):
    """Bounded content-keyed LRU shared by the host-side caches below."""
    hit = cache.get(key)
    if hit is not None:
        cache.move_to_end(key)
        return hit
    out = cache[key] = fn()
    while len(cache) > maxsize:
        cache.popitem(last=False)
    return out


# Content-keyed device-placement cache.  A sweep's grid points mostly
# share a FabricSpec, so the route/capacity/incidence tensors of every
# point are byte-identical; hashing is cheaper than re-uploading (and
# than re-sorting the incidence).  Keys carry shape + dtype + digest, so
# two different tensors never alias.  Bounded LRU: a long-lived process
# sweeping many fabrics cannot leak device memory.
_PUT_CACHE: "collections.OrderedDict[tuple, jnp.ndarray]" = \
    collections.OrderedDict()
_PUT_CACHE_SIZE = 256

_INC_CACHE: "collections.OrderedDict[tuple, tuple]" = collections.OrderedDict()
_INC_CACHE_SIZE = 128


def _cached_put(x: np.ndarray, dtype) -> jnp.ndarray:
    x = np.ascontiguousarray(np.asarray(x, dtype))
    return _memo_lru(_PUT_CACHE, _PUT_CACHE_SIZE, _digest(x),
                     lambda: jnp.asarray(x))


def _incidence_key(alt_routes: np.ndarray, n_links: int,
                   vc: np.ndarray | None, n_vcs: int) -> tuple:
    key = _digest(alt_routes) + (n_links, n_vcs)
    if n_vcs > 1 and vc is not None:
        key = key + _digest(vc)
    return key


def _incidence(alt_routes: np.ndarray, n_links: int,
               vc: np.ndarray | None = None, n_vcs: int = 1):
    """``link_incidence`` memoised on route-stack content (the sort is
    O(FKH log FKH) on host; grid points sharing a fabric pay it once).
    The key carries the VC layout too: the same routes under a
    different VC assignment sort into different (wire, VC) queues."""
    return _memo_lru(_INC_CACHE, _INC_CACHE_SIZE,
                     _incidence_key(alt_routes, n_links, vc, n_vcs),
                     lambda: link_incidence(alt_routes, n_links,
                                            vc=vc, n_vcs=n_vcs))


def _pool_incidence(sink_switch: np.ndarray, n_switches: int):
    """Link ids stably sorted by sink switch (-1 hosts -> scratch)."""
    seg = np.where(sink_switch >= 0, sink_switch, n_switches)
    perm = np.argsort(seg, kind="stable").astype(np.int32)
    return perm, seg[perm].astype(np.int32)


#: Longest per-link contributor list the dense reduction will tile; more
#: skewed scenarios (massive incast onto one link) fall back to the
#: sorted segment-sum engine.
DENSE_ROWS_CAP = 1024


def clamp_dense_rows(ml: int, n_links: int, n_entries: int) -> int:
    """Apply the dense-CSR size guard to a row count (0 = disable).

    One guard for single scenarios AND batches: a batch must re-clamp
    its *maximum* per-run row count here, otherwise one high-skew run
    would drag every run onto an oversized [L, rows] table the
    per-scenario check was meant to refuse.
    """
    if ml == 0 or ml > DENSE_ROWS_CAP:
        return 0
    if n_links * ml > max(16 * n_entries, 1 << 20):
        return 0
    return ml


def _scenario_vc(scn: Scenario, alt_routes: np.ndarray,
                 n_vcs: int) -> np.ndarray:
    """Validated [F, K, H] VC tensor for a scenario (all-zero default).

    ``n_vcs = 1`` always collapses to VC 0 — running a VC-annotated
    scenario under a single-VC config degenerates to the shared-queue
    model, by design.  With more VCs the assignment must fit, and PAD
    hops are forced to VC 0 so they land on the incidence scratch
    segment exactly.
    """
    if n_vcs == 1 or scn.vc is None:
        return np.zeros(alt_routes.shape, np.int32)
    vc = np.asarray(scn.vc, np.int32)
    if vc.shape != alt_routes.shape:
        raise ValueError(
            f"Scenario.vc shape {vc.shape} != candidate stack shape "
            f"{alt_routes.shape}")
    if vc.min(initial=0) < 0 or vc.max(initial=0) >= n_vcs:
        raise ValueError(
            f"Scenario.vc entries must lie in [0, {n_vcs}) "
            f"(got [{vc.min()}, {vc.max()}]); rebuild the assignment "
            f"for this n_vcs (routing.assign_vc clips for you)")
    return np.where(alt_routes == PAD, 0, vc).astype(np.int32)


def _queue_incidence(scn: Scenario, n_vcs: int):
    """``(key, perm, off, S)``: a scenario's per-queue incidence
    (``link_incidence``, memoised) with its content key."""
    alt = scn.routes[:, None, :] if scn.alt_routes is None \
        else scn.alt_routes
    alt = np.asarray(alt, np.int32)
    L = scn.capacity.shape[0]
    vc = _scenario_vc(scn, alt, n_vcs)
    key = _incidence_key(alt, L, vc, n_vcs)
    perm, _, off = _memo_lru(_INC_CACHE, _INC_CACHE_SIZE, key,
                             lambda: link_incidence(alt, L, vc=vc,
                                                    n_vcs=n_vcs))
    return key, perm, off, L * n_vcs


def dense_reduce_rows(scn: Scenario, n_vcs: int = 1) -> int:
    """Longest per-queue contributor list, for the dense reduction
    (0 = disable).

    The fused reduction can run scatter-free: gather each (wire, VC)
    queue's (sorted) contributors position by position and accumulate
    positions left-to-right — bit-identical to the sequential scatter,
    but pure gathers + vector adds (``dense_layout``).  Scenarios whose
    longest list passes ``DENSE_ROWS_CAP`` — or whose rectangle of
    queues x that many positions would dwarf the incidence itself —
    report 0 and use the segment-sum engine.
    """
    if scn.capacity.shape[0] == 0:
        return 0
    _, perm, off, S = _queue_incidence(scn, n_vcs)
    ml = int(np.max(off[1:S + 1] - off[:S]))
    return clamp_dense_rows(ml, S, perm.size)


def jagged_blocks(counts: np.ndarray) -> tuple[tuple[int, int], ...]:
    """Block shapes ``((width, positions), ...)`` of the jagged-diagonal
    reduction layout for ``[R, S]`` per-queue contributor counts.

    Each run ranks its queues by contributor count, longest first.
    Position p is ``w_p`` slots wide: the most queues, over the runs,
    that hold more than p contributors, so no slot is kept for a queue
    that is empty there in every run.  Consecutive positions share a
    block (one small ``[positions, width]`` rectangle at the block's
    first, widest, width) until the width falls to half of that or
    below, which keeps the padding under the real slots.
    """
    counts = np.asarray(counts, np.int64)
    counts = counts.reshape(-1, counts.shape[-1])
    P = int(counts.max(initial=0))
    S = counts.shape[1]
    pos = np.arange(P)
    w = np.max([S - np.searchsorted(np.sort(c), pos, side="right")
                for c in counts], axis=0)
    blocks, p = [], 0
    while p < P:
        n = 1
        while p + n < P and 2 * w[p + n] > w[p]:
            n += 1
        blocks.append((int(w[p]), n))
        p += n
    return tuple(blocks)


def jagged_index(perm: np.ndarray, off: np.ndarray, n_queues: int,
                 blocks: tuple) -> tuple[np.ndarray, np.ndarray]:
    """One run's ``(idx, back)`` under the block shapes ``blocks``.

    ``idx`` [sum of width x positions] lists, block by block, position
    by position, the rank-ordered queues' contributor rows in the
    flattened ``[F*K*H]`` channel order (``perm`` composed in); a slot
    past its queue's list reads the sentinel ``F*K*H``.  ``back``
    [S + 1] maps each queue (and the scratch slot S) to its rank among
    the first block's rows, or to the zero row past them.  A layout
    whose slots cannot hold every contributor raises.
    """
    N = perm.shape[0]
    cnt = (off[1:n_queues + 1] - off[:n_queues]).astype(np.int64)
    order = np.argsort(-cnt, kind="stable")
    idx, p0 = [], 0
    for w, n in blocks:
        q = order[:w]
        p = p0 + np.arange(n)[:, None]
        ok = p < cnt[q][None, :]
        idx.append(np.where(ok, perm[np.where(ok, off[q] + p, 0)], N)
                   .reshape(-1))
        p0 += n
    idx = np.concatenate(idx).astype(np.int32)
    if np.count_nonzero(idx != N) != cnt.sum():
        raise ValueError(
            f"reduction layout {blocks} cannot hold every contributor "
            f"(longest queue list {cnt.max(initial=0)})")
    W = blocks[0][0]
    rank = np.empty((n_queues,), np.int64)
    rank[order] = np.arange(n_queues)
    back = np.append(np.minimum(rank, W), W).astype(np.int32)
    return idx, back


#: Jagged layouts of whole batches, keyed on their runs' incidence.
_LAYOUT_CACHE: "collections.OrderedDict[tuple, tuple]" = \
    collections.OrderedDict()
_LAYOUT_CACHE_SIZE = 32


def dense_layout(scns, n_vcs: int = 1, rows: int | None = None):
    """``(blocks, red_idx [R, T], red_back [R, S + 1], n_rows)`` of the
    dense reduction over a batch of same-shape (padded) scenarios.

    ``rows=None`` derives the jagged blocks from the batch's counts
    (``jagged_blocks``); an explicit ``rows`` is the plain rectangle,
    one block of every queue x ``rows`` positions, whose shape does not
    depend on the batch's content.  ``n_rows`` counts the real
    contributors over the runs.  Memoised on the runs' incidence
    content, and uploaded through the placement cache, so relaunching
    a batch stages nothing new.
    """
    incs = [_queue_incidence(s, n_vcs) for s in scns]
    key = (tuple(k for k, *_ in incs), rows)

    def build():
        S = incs[0][3]
        counts = np.stack([off[1:S + 1] - off[:S] for _, _, off, _ in incs])
        blocks = jagged_blocks(counts) if rows is None \
            else ((S, int(rows)),)
        idx, back = zip(*(jagged_index(perm, off, S, blocks)
                          for _, perm, off, _ in incs))
        return (blocks, _cached_put(np.stack(idx), np.int32),
                _cached_put(np.stack(back), np.int32), int(counts.sum()))

    return _memo_lru(_LAYOUT_CACHE, _LAYOUT_CACHE_SIZE, key, build)


def dense_engine(scns, n_vcs: int, rows: int, *, pinned: bool):
    """``(dense_blocks, layout)``: the dense reduction a batch of
    padded scenarios runs with.

    ``rows`` is the batch's longest contributor list, or a pinned row
    count; 0 keeps the segment-sum engine (``((), None)``).  Otherwise
    ``layout`` is ``(red_idx, red_back, n_rows)`` of ``dense_layout``:
    the jagged layout, or with ``pinned`` the rectangle of every queue
    x ``rows`` positions.
    """
    if not rows:
        return (), None
    blocks, idx, back, n_rows = dense_layout(
        scns, n_vcs, int(rows) if pinned else None)
    return blocks, (idx, back, n_rows)


def jagged_sums(data: jnp.ndarray, idx: jnp.ndarray, back: jnp.ndarray,
                blocks: tuple) -> list:
    """Per-queue sums ``[S + 1]`` of each channel of ``data`` [N, C]
    under one run's jagged layout (``dense_layout``).

    One gather of every slot straight from the channels (the sentinel
    N reads an appended zero row), then, block by block, positions
    added left to right onto the first ``width`` ranked queues (a loop
    over a block's positions: on a v5e it beat the unrolled chain), then
    each queue's sum taken back from its rank (empty queues and the
    scratch slot read 0).  Every queue adds ``0 + d0 + d1 + ...`` in
    its incidence order: bit-identical to the sequential scatter.
    """
    C = data.shape[-1]
    zero = jnp.zeros((1, C), jnp.float32)
    table = jnp.take(jnp.concatenate([data, zero]), idx, axis=0)
    W = blocks[0][0]
    acc = jnp.zeros((W, C), jnp.float32)
    start = 0
    for w, n in blocks:
        tab = table[start:start + w * n].reshape(n, w, C)
        start += w * n
        if n == 1:
            head = acc[:w] + tab[0]
        else:
            head = jax.lax.fori_loop(
                0, n, lambda p, h, tab=tab: h + jax.lax.dynamic_index_in_dim(
                    tab, p, keepdims=False), acc[:w])
        acc = head if w == W else jnp.concatenate([head, acc[w:]])
    sums = jnp.take(jnp.concatenate([acc, zero]), back, axis=0)
    return [sums[:, c] for c in range(C)]


def scenario_device(scn: Scenario, n_vcs: int = 1) -> ScenarioDev:
    """Move one scenario's tensors to device-ready arrays.

    Fabric-shaped tensors (routes, capacities, incidence) go through a
    content-keyed placement cache: grid points sharing a ``FabricSpec``
    upload them once instead of once per point.  ``n_vcs`` (static,
    from ``LinkParams.n_vcs``) keys the incidence by (wire, VC) queue;
    the default 1 is byte-identical to the legacy single-queue layout.
    """
    if scn.alt_routes is None:          # single-path: K = 1 mirror
        alt_routes = scn.routes[:, None, :]
        alt_hops = scn.hops[:, None]
    else:
        alt_routes, alt_hops = scn.alt_routes, scn.alt_hops
    alt_routes = np.asarray(alt_routes, np.int32)
    F = scn.routes.shape[0]
    L = scn.capacity.shape[0]
    vc = _scenario_vc(scn, alt_routes, n_vcs)
    perm, seg, off = _incidence(alt_routes, L, vc, n_vcs)
    pool_perm, pool_seg = _pool_incidence(
        np.asarray(scn.sink_switch, np.int32), int(scn.n_switches))
    return ScenarioDev(
        alt_routes=_cached_put(alt_routes, np.int32),
        alt_hops=_cached_put(alt_hops, np.int32),
        vc=_cached_put(vc, np.int32),
        gen_rate=jnp.asarray(scn.gen_rate, jnp.float32),
        t_start=jnp.asarray(scn.t_start, jnp.float32),
        t_stop=jnp.asarray(scn.t_stop, jnp.float32),
        volume=jnp.asarray(scn.volume, jnp.float32),
        cap_ext=_cached_put(
            np.concatenate([scn.capacity, [np.inf]]), np.float32),
        sink_ext=_cached_put(
            np.concatenate([scn.sink_switch, [-1]]), np.int32),
        rtt=jnp.asarray(scn.rtt_steps, jnp.int32),
        # broadcast to [F] so scalar- and per-flow-buffer scenarios share
        # one device shape (batched sweeps stack them along a run axis)
        nic_buffer=jnp.broadcast_to(
            jnp.asarray(scn.nic_buffer, jnp.float32),
            scn.routes.shape[:1]),
        jitter=_cached_put(_flow_jitter(F), np.float32),
        red_perm=_cached_put(perm, np.int32),
        red_seg=_cached_put(seg, np.int32),
        red_off=_cached_put(off, np.int32),
        pool_perm=_cached_put(pool_perm, np.int32),
        pool_seg=_cached_put(pool_seg, np.int32),
    )


def step_params(cfg: "CCConfig | CCSpec", *,
                temperature: float = 0.0) -> StepParams:
    """Flatten a config into the traced scalars ``fluid_step`` reads.

    Accepts the legacy ``CCConfig`` (mapped through ``to_spec()``, the
    bit-exact shim) or a ``CCSpec`` directly.  Stage names resolve to
    registry codes; each family's param union comes from the registered
    stages' extractors.  ``temperature`` selects the soft-relaxed
    dynamics (``repro.tune``); the default 0 is the exact hard model.
    """
    spec: CCSpec = cfg.to_spec()
    lk = spec.link
    route_code = ROUTING_MODES.index(spec.routing)
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    return StepParams(
        mark_code=jnp.asarray(cc.MARKING.code(spec.marking), jnp.int32),
        notif_code=jnp.asarray(cc.NOTIFICATION.code(spec.notification),
                               jnp.int32),
        react_code=jnp.asarray(cc.REACTION.code(spec.reaction), jnp.int32),
        route_code=jnp.asarray(route_code, jnp.int32),
        line_rate=f32(lk.line_rate),
        xoff=f32(lk.port_buffer * lk.pfc_xoff_frac),
        xon=f32(lk.port_buffer * lk.pfc_xon_frac),
        pool_xoff=f32(lk.shared_buffer * lk.pfc_xoff_frac),
        port_buffer=f32(lk.port_buffer),
        ecp_beta=f32(spec.rev.ecp_rate_ewma),
        mark=cc.MARKING.device_params(spec),
        notif=cc.NOTIFICATION.device_params(spec),
        react=cc.REACTION.device_params(spec),
        temperature=f32(temperature),
    )


def check_routing_paths(cfg: "CCConfig | CCSpec", scn: Scenario) -> None:
    """Adaptive routing needs detour candidates to select from.

    ``routing != "min"`` on a single-path scenario would silently
    degenerate to minimal routing (there is nothing to pick); raise at
    the point where config meets scenario instead.
    """
    K = 1 if scn.alt_routes is None else scn.alt_routes.shape[1]
    if cfg.routing != "min" and K == 1:
        raise ValueError(
            f"routing={cfg.routing!r} needs a multi-path scenario with "
            f"detour candidates (build it with ScenarioSpec(n_paths > 1) "
            f"or Scenario.alt_routes); this scenario is single-path")


def init_state(scn: Scenario, cfg: "CCConfig | CCSpec",
               delay_slots: int | None = None) -> FluidState:
    F, H = scn.routes.shape
    L = scn.capacity.shape[0]
    V = int(getattr(cfg.link, "n_vcs", 1))
    D = delay_depth(scn) if delay_slots is None \
        else _check_delay(scn, delay_slots)
    line = jnp.asarray(np.minimum(scn.gen_rate, cfg.link.line_rate),
                       jnp.float32)
    z_f = jnp.zeros((F,), jnp.float32)
    return FluidState(
        qh=jnp.zeros((F, H), jnp.float32),
        nicq=z_f, delivered=z_f, offered=z_f, dropped=z_f,
        est=jnp.zeros((F, H), jnp.float32),
        paused=jnp.zeros((L * V,), jnp.float32),
        rate=line,
        rp_target=line,
        alpha=jnp.full((F,), cfg.dcqcn.alpha_init, jnp.float32),
        byte_cnt=z_f, tmr=z_f, alpha_tmr=z_f,
        bc_stage=jnp.zeros((F,), jnp.int32),
        t_stage=jnp.zeros((F,), jnp.int32),
        hold=z_f, np_tmr=jnp.full((F,), 1.0, jnp.float32),
        trig_buf=jnp.zeros((D, F), jnp.float32),
        tgt_buf=jnp.zeros((D, F), jnp.float32),
        path_idx=jnp.zeros((F,), jnp.int32),
        cc=cc.init_cc_state(scn),
        t=jnp.zeros((), jnp.int32),
    )


def fluid_step(st: FluidState, sd: ScenarioDev, par: StepParams, *,
               dt: float, n_switches: int, reduce: str = "fused",
               dense_blocks: tuple = (), n_vcs: int = 1):
    """One ``dt`` update: (state, scenario, params) -> (state, trace).

    Pure in all array arguments; ``dt`` / ``n_switches`` and the
    pipeline switches are static.  ``sd`` and ``par`` are data, so a
    sweep vmaps this over a leading run axis with a single compilation.

    ``reduce`` picks the per-link reduction engine:
      * ``"fused"`` (default) — every per-link sum rides one of three
        multi-channel sorted segment reductions over the precomputed
        incidence (``sd.red_perm``/``red_seg``), bit-identical to the
        scatter path (stable sort preserves each link's contributor
        order; interleaved +0.0 terms from unselected candidates are
        exact no-ops).
      * ``"scat"`` — the legacy one-scatter-per-quantity path, kept as
        the parity/benchmark baseline.

    ``dense_blocks`` (static, the block shapes of ``dense_layout``)
    upgrades the ``"fused"`` engine to the scatter-free dense form:
    each pass gathers the contributors of the jagged layout in
    ``sd.red_idx`` straight from the channels and accumulates positions
    left-to-right (``jagged_sums``) — the fastest path when link load
    is not pathologically skewed, still bit-identical.  ``()`` keeps
    the segment-sum engine.

    ``n_vcs`` (static, ``LinkParams.n_vcs``) splits every wire's input
    buffer into that many virtual-channel queues with independent
    backlog, FIFO order and PFC pause state (per-VC thresholds =
    port thresholds / n_vcs); wire *capacity* stays shared, served
    across VCs in proportion to drainable backlog.  Per-wire
    quantities (fair grants, oversubscription, the shared pool, UGAL
    path cost) are per-VC sums folded back per wire.  ``n_vcs = 1``
    takes statically identical code paths to the legacy single-queue
    model — bitwise, not just numerically.
    """
    if reduce not in ("fused", "scat"):
        raise ValueError(
            f"reduce must be 'fused' or 'scat', got {reduce!r}")
    fused = reduce != "scat"
    F, K, H = sd.alt_routes.shape
    L = sd.cap_ext.shape[0] - 1
    V = int(n_vcs)
    S = L * V                 # (wire, VC) queue count; S == L when V == 1
    D = st.trig_buf.shape[0]
    dt = jnp.float32(dt)

    def to_wire(x_ext):
        """Fold a per-queue [S + 1] sum to per-wire [L + 1] (keep
        scratch).  Static identity at V == 1 — zero graph change."""
        if V == 1:
            return x_ext
        return jnp.concatenate(
            [x_ext[:S].reshape(L, V).sum(axis=1), x_ext[S:]])

    def to_queues(x_wire):
        """Spread a per-wire [L + 1] vector over the wire's V queues,
        [S + 1] (scratch L -> S).  Static identity at V == 1."""
        if V == 1:
            return x_wire
        return jnp.concatenate([jnp.repeat(x_wire[:L], V), x_wire[L:]])

    def hop_reads(*cols):
        """Read [S + 1] per-queue vectors at every flow's hops in ONE
        gather (the columns of ``[C, S + 1]`` by ``qidx``: on the chip
        the cost is per gather and per index), one [F, H] each.
        Per-wire values come in through ``to_queues``: ``qidx == S``
        exactly where ``widx == L``, so each hop reads the float that
        ``x[widx]`` reads."""
        cols_fh = jnp.stack(cols)[:, qidx]                    # [C, F, H]
        return [cols_fh[c] for c in range(len(cols))]
    # soft-relaxation temperature: every hard gate below is written
    # ``soft.select(tau, soft_expr, hard_expr)`` with the hard branch
    # verbatim, so tau == 0 is bitwise the hard model (repro.tune).
    tau = par.temperature

    _ah, _fi = _index_consts(F, H)
    arange_h = jnp.asarray(_ah)
    fidx = jnp.asarray(_fi)
    t_sec = st.t.astype(jnp.float32) * dt

    def pick_paths(k_idx):
        """([F, H] routes, [F] hops) of candidate ``k_idx`` per flow."""
        r = jnp.take_along_axis(sd.alt_routes, k_idx[:, None, None],
                                axis=1)[:, 0]
        h = jnp.take_along_axis(sd.alt_hops, k_idx[:, None], axis=1)[:, 0]
        return r, h

    def link_sums(channels, k_sel):
        """All per-queue sums of the [F, H] ``channels`` in ONE sweep.

        Channels are laid out on candidate slot ``k_sel`` per flow
        (zeros elsewhere) and gathered into the queue-sorted incidence
        order; one [F*K*H, C] pass produces every [S+1] per-(wire, VC)
        vector at once instead of C scatters (S == L when V == 1, in
        which case "queue" is just "wire").  The pass is summed by
        the jagged dense layout or a sorted segment sum — both
        accumulate each queue's contributors in the same order, so the
        result is bit-identical across engines.
        """
        with obs.scope("fluid.reduce"):
            data = jnp.stack(channels, axis=-1)                 # [F, H, C]
            C = data.shape[-1]
            if K > 1:
                onehot = (jnp.arange(K, dtype=jnp.int32)[None, :]
                          == k_sel[:, None])                    # [F, K]
                data = data[:, None] * \
                    onehot[:, :, None, None].astype(jnp.float32)
            data = data.reshape(F * K * H, C)
            if dense_blocks:
                return jagged_sums(data, sd.red_idx, sd.red_back,
                                   dense_blocks)
            data = jnp.take(data, sd.red_perm, axis=0)
            sums = jax.ops.segment_sum(data, sd.red_seg,
                                       num_segments=S + 1,
                                       indices_are_sorted=True)
            return [sums[:, c] for c in range(C)]

    # ---- 0. path selection (min / valiant / ugal) -------------------------
    with obs.scope("fluid.select"):
        if K == 1:
            # single-path scenario: selection is statically a no-op, and the
            # update below is the exact single-table computation.
            path_idx = st.path_idx
            routes, hops = sd.alt_routes[:, 0, :], sd.alt_hops[:, 0]
        else:
            # Per-link backlog of the *pre-step* queues, laid out along each
            # flow's currently selected path (its queued bytes live there).
            routes_old, hops_old = pick_paths(st.path_idx)
            v_old = routes_old != PAD
            hq_old = v_old & (arange_h < (hops_old[:, None] - 1))
            if fused:
                (B_prev,) = link_sums([jnp.where(hq_old, st.qh, 0.0)],
                                      st.path_idx)
                B_prev = to_wire(B_prev)
            elif V == 1:
                B_prev = jnp.zeros((L + 1,), jnp.float32).at[
                    jnp.where(v_old, routes_old, L)].add(
                        jnp.where(hq_old, st.qh, 0.0))
            else:
                vc_old = jnp.take_along_axis(
                    sd.vc, st.path_idx[:, None, None], axis=1)[:, 0]
                B_prev = to_wire(jnp.zeros((S + 1,), jnp.float32).at[
                    jnp.where(v_old, routes_old * V + vc_old, S)].add(
                        jnp.where(hq_old, st.qh, 0.0)))

            def path_cost(k_idx):
                """UGAL cost: hop count x backlog along the candidate."""
                r, h = pick_paths(k_idx)
                v = r != PAD
                q = jnp.sum(jnp.where(v, B_prev[jnp.where(v, r, L)], 0.0),
                            axis=1)
                return h.astype(jnp.float32) * q

            # one sampled detour per flow, rotating over its valid slots
            # (slots 1..n_alt; flows without candidates stay minimal)
            n_alt = jnp.sum((sd.alt_hops[:, 1:] > 0).astype(jnp.int32), axis=1)
            samp = jnp.where(n_alt > 0,
                             1 + (fidx + st.t) % jnp.maximum(n_alt, 1), 0)
            # UGAL-L: switch only if the detour's queue-weighted hops beat
            # the minimal path's STRICTLY — ties (e.g. zero backlog
            # everywhere) keep the minimal route.
            ugal_pick = jnp.where(path_cost(samp) < path_cost(
                jnp.zeros((F,), jnp.int32)), samp, 0)
            # selection epochs: flow start (both modes) + CNP arrival (ugal
            # re-evaluates under congestion feedback).  Reading the delay
            # line here matches phase 5's cnp exactly: this step's emissions
            # land at (t + rtt) % D != t % D since 0 < rtt < D.
            starting = (t_sec >= sd.t_start) & (t_sec - dt < sd.t_start)
            cnp_now = st.trig_buf[st.t % D] > 0
            epoch = starting | ((par.route_code == 2) & cnp_now)
            pick = jnp.where(par.route_code == 1, samp, ugal_pick)
            path_idx = jnp.where(par.route_code == 0, 0,
                                 jnp.where(epoch, pick, st.path_idx))
            routes, hops = pick_paths(path_idx)

        valid = routes != PAD
        widx = jnp.where(valid, routes, L)         # PAD -> scratch slot L
        if V == 1:
            qidx = widx                            # queue == wire, verbatim
        else:
            # VC of the selected candidate per hop; PAD hops carry VC 0
            # (enforced host-side), so qidx == S exactly at the scratch.
            vc_sel = sd.vc[:, 0, :] if K == 1 else jnp.take_along_axis(
                sd.vc, path_idx[:, None, None], axis=1)[:, 0]
            qidx = jnp.where(valid, widx * V + vc_sel, S)
        is_last = valid & (arange_h == (hops[:, None] - 1))
        holds_queue = valid & (arange_h < (hops[:, None] - 1))
        eps_rate = jnp.float32(1e6)                # B/s: "active" demand

    def scat(values_fh, init=0.0):
        """Scatter-add a [F,H] quantity onto per-queue slots [S+1]."""
        with obs.scope("fluid.reduce"):
            out = jnp.full((S + 1,), init, jnp.float32)
            return out.at[qidx].add(values_fh)

    # ---- 1. generation ----------------------------------------------------
    with obs.scope("fluid.generate"):
        active = (t_sec >= sd.t_start) & (t_sec < sd.t_stop)
        gen = jnp.where(active, sd.gen_rate, 0.0) * dt
        gen = jnp.minimum(gen, jnp.maximum(sd.volume - st.offered, 0.0))
        nicq = st.nicq + gen
        over = jnp.maximum(nicq - sd.nic_buffer, 0.0)
        nicq = nicq - over
        offered = st.offered + gen - over
        dropped = st.dropped + over
        np_tmr_t = st.np_tmr + dt                  # notification-window tick

    # ---- 2. transfers -----------------------------------------------------
    with obs.scope("fluid.transfer"):
        src_inj = jnp.minimum(nicq, jnp.minimum(st.rate, par.line_rate) * dt)
        src_q = jnp.concatenate([src_inj[:, None], st.qh[:, :-1]], axis=1)
        src_q = jnp.where(valid, src_q, 0.0)

        pause_q = jnp.concatenate([st.paused, jnp.zeros((1,), jnp.float32)])
        pause_h, caps_w = hop_reads(pause_q, to_queues(sd.cap_ext))
        wire_open = 1.0 - pause_h                          # [F,H] 1 = drainable

        # strict-FIFO HoL factor per link queue: share of the queue whose
        # *next* wire is currently drainable.  ``wire_open`` is an exact
        # 0/1 float in hard mode; a fractional pause level scales service
        # proportionally (the fluid relaxation of the on/off gate).
        next_open = jnp.concatenate(
            [wire_open[:, 1:], jnp.ones((F, 1), jnp.float32)], axis=1)
        q_here = jnp.where(holds_queue, st.qh, 0.0)        # queue at sink(h)
        weight = src_q * wire_open
        if fused:
            num, den, sum_w = link_sums(
                [q_here * next_open, q_here, weight], path_idx)
        else:
            num = scat(q_here * next_open)
            den = scat(q_here)
            sum_w = scat(weight)
        # FIFO factor is per (wire, VC) queue — a paused-head VC no longer
        # stalls its siblings, only its own lane (the HoL fix VCs buy).
        fifo_ok = jnp.where(den > 0, fdiv(num, jnp.maximum(den, 1e-9)), 1.0)
        # ... but the byte budget is per *wire*: capacity is shared across
        # VCs in proportion to drainable backlog.  fifo_ok <= 1, so the
        # summed per-VC grants never exceed the wire's C*dt.
        fifo_h, sum_w_h = hop_reads(fifo_ok, to_queues(to_wire(sum_w)))

        budget = caps_w * dt * fifo_h
        share = jnp.where(sum_w_h > 0,
                          fdiv(budget * weight, jnp.maximum(sum_w_h, 1e-9)),
                          0.0)
        T = jnp.minimum(weight, share)                     # bytes crossing h

        nicq = nicq - T[:, 0]
        qh = st.qh - jnp.pad(T[:, 1:], ((0, 0), (0, 1)))   # drain from h-1
        qh = qh + jnp.where(holds_queue, T, 0.0)           # land at sink(h)
        qh = jnp.maximum(qh, 0.0)
        deliv_step = jnp.sum(jnp.where(is_last, T, 0.0), axis=1)
        delivered = st.delivered + deliv_step

        # crossing-rate EWMA (doubles as arrival-into-queue estimate)
        est = (1 - par.ecp_beta) * st.est + par.ecp_beta * fdiv(T, dt)

        # Demand to cross wire h = arrival rate into the queue feeding it
        # (pre-stall, so FIFO-blocked victims keep their true demand).
        # Computed here so the post-transfer reduction pass covers the PFC
        # sink queues AND the marking activity sums in one sweep.
        dem = jnp.concatenate([est[:, :1], est[:, :-1]], axis=1)
        dem = jnp.where(valid, dem, 0.0)
        act = (dem > eps_rate) & valid

    # ---- 3. PFC -----------------------------------------------------------
    with obs.scope("fluid.pfc"):
        if fused:
            B_ext, n_act, sum_dem = link_sums(
                [jnp.where(holds_queue, qh, 0.0),
                 act.astype(jnp.float32),
                 jnp.where(act, dem, 0.0)], path_idx)
            B = B_ext[:S]                           # [S] per-(wire, VC) queues
        else:
            B = scat(jnp.where(holds_queue, qh, 0.0))[:S]
            n_act = scat(act.astype(jnp.float32), init=0.0)
            sum_dem = scat(jnp.where(act, dem, 0.0))
        # xoff/xon hysteresis per queue: hard = set above xoff, clear below
        # xon, hold in between; soft = the pause level relaxes toward 1 (0)
        # through a sigmoid band O(tau * port_buffer) wide around each
        # threshold.  With V > 1 the port thresholds split evenly across
        # the VC queues (static branch — V == 1 keeps the exact scalars).
        if V == 1:
            xoff_q, xon_q = par.xoff, par.xon
        else:
            xoff_q, xon_q = par.xoff / V, par.xon / V
        paused_h = jnp.where(B > xoff_q, 1.0,
                             jnp.where(B < xon_q, 0.0, st.paused))
        g_on = soft.unit_gate(B - xoff_q, tau, par.port_buffer)
        g_off = soft.unit_gate(xon_q - B, tau, par.port_buffer)
        paused_s = st.paused + (1.0 - st.paused) * g_on - st.paused * g_off
        paused = soft.select(tau, paused_s, paused_h)
        sink_l = sd.sink_ext[:L]
        # shared pool counts the wire's whole input buffer across its VCs
        B_wire = B if V == 1 else B.reshape(L, V).sum(axis=1)
        with obs.scope("fluid.reduce"):
            if fused:
                pool = jax.ops.segment_sum(
                    jnp.take(jnp.where(sink_l >= 0, B_wire, 0.0), sd.pool_perm),
                    sd.pool_seg, num_segments=n_switches + 1,
                    indices_are_sorted=True)[:n_switches]
            else:
                pool = jnp.zeros((n_switches,), jnp.float32).at[
                    jnp.maximum(sink_l, 0)].add(
                        jnp.where(sink_l >= 0, B_wire, 0.0))
        pool_hot = soft.select(
            tau,
            soft.unit_gate(pool - par.pool_xoff, tau, par.port_buffer),
            (pool > par.pool_xoff).astype(jnp.float32))
        # max of pause levels == boolean OR on the exact 0/1 hard values;
        # a hot pool pauses every VC of the wire (pause is per-queue state)
        pool_pause = jnp.where(sink_l >= 0,
                               pool_hot[jnp.maximum(sink_l, 0)], 0.0)
        if V > 1:
            pool_pause = jnp.repeat(pool_pause, V)
        paused = jnp.maximum(paused, pool_pause)

    # ---- 4. marking (cc.MARKING dispatch) ---------------------------------
    with obs.scope("fluid.mark"):
        # B1_w: occupancy of the flow's own (wire, VC) queue — marking sees
        # the lane the flow actually sits in, not its siblings' backlog;
        # the fair grants / oversubscription below are per-wire notions
        B1 = jnp.concatenate([B, jnp.zeros((1,), jnp.float32)])
        B1_w, n_act_h, sum_dem_h = hop_reads(
            B1, to_queues(to_wire(n_act)), to_queues(to_wire(sum_dem)))
        present = (qh > 0) | (T > 0)

        share0 = fdiv(caps_w, jnp.maximum(n_act_h, 1.0))
        under = dem < share0
        if fused:
            surplus, n_heavy = link_sums(
                [jnp.where(act & under, share0 - dem, 0.0),
                 (act & ~under).astype(jnp.float32)], path_idx)
        else:
            surplus = scat(jnp.where(act & under, share0 - dem, 0.0))
            n_heavy = scat((act & ~under).astype(jnp.float32))
        surplus_h, n_heavy_h = hop_reads(to_queues(to_wire(surplus)),
                                         to_queues(to_wire(n_heavy)))
        grant = jnp.where(
            under, dem, share0 + fdiv(surplus_h, jnp.maximum(n_heavy_h, 1.0)))
        grant = jnp.where(act, grant, caps_w)
        # wire h oversubscribed?  (soft: sigmoid in the demand excess; the
        # PAD slot's cap is inf, so the soft gate is exactly 0 there too)
        oversub = soft.select(
            tau,
            soft.unit_gate(sum_dem_h - caps_w, tau, par.line_rate),
            (sum_dem_h > caps_w).astype(jnp.float32))
        # ... all shifted to the *next* wire (the flow's requested output)
        inf_col = jnp.full((F, 1), jnp.inf, jnp.float32)
        grant_next = jnp.concatenate([grant[:, 1:], inf_col], axis=1)
        grant_next = jnp.where(holds_queue, grant_next, jnp.inf)
        dem_next = jnp.concatenate(
            [dem[:, 1:], jnp.zeros((F, 1), jnp.float32)], axis=1)
        over_next = jnp.concatenate(
            [oversub[:, 1:], jnp.zeros((F, 1), jnp.float32)], axis=1)

        # Every registered marking stage (CP occupancy / ECP fair-grant /
        # slope ramp / ...) computes its mark set + severity from this
        # shared context; the traced ``mark_code`` selects one — so marking
        # joins scheme constants and routing as a one-launch sweep axis.
        (mark_fh, sev), cc_mark = cc.dispatch(
            cc.MARKING, par.mark_code, par.mark,
            cc.MarkCtx(B1_w=B1_w, present=present, holds_queue=holds_queue,
                       dem_next=dem_next, grant_next=grant_next,
                       over_next=over_next, port_buffer=par.port_buffer,
                       line_rate=par.line_rate, tau=tau),
            st.cc)
        # mark_fh is a [F, H] float mark intensity: exact 0/1 in hard mode,
        # sigmoid-graded under the soft model.
        mark_pos = mark_fh > 0.0
        marked = jnp.any(mark_pos, axis=1)
        # severity payload: fair grant at the marking queue, scaled down by
        # the queue's excess over V so standing backlog drains (ENP carries
        # "timely congestion severity", ERP converges to fair as B -> V).
        # Hard: min over marking hops.  Soft: intensity-weighted mean —
        # inf sentinels (non-queue hops) carry zero intensity and are
        # where-masked out, never multiplied (0 * inf = nan).
        tgt_h = jnp.min(jnp.where(mark_pos, sev, jnp.inf), axis=1)
        tgt_h = jnp.where(jnp.isfinite(tgt_h), tgt_h, par.line_rate)
        # inf severities (a marking hop whose next wire has no finite
        # grant) take the same line-rate fallback as the hard min above —
        # inside the mask, so the weighted mean never touches inf
        sev_fin = jnp.where(jnp.isfinite(sev), sev, par.line_rate)
        m_sev = jnp.sum(jnp.where(mark_pos, mark_fh * sev_fin, 0.0), axis=1)
        m_sum = jnp.sum(mark_fh, axis=1)
        tgt = soft.select(
            tau, (m_sev + 1e-6 * par.line_rate) / (m_sum + 1e-6), tgt_h)
        # notification sees a [F] mark level: any-hop in hard mode, the
        # peak intensity (capped at one message) under the soft model
        mark_lvl = jnp.minimum(jnp.max(mark_fh, axis=1), 1.0)

    # ---- 5. notification (cc.NOTIFICATION dispatch) -----------------------
    with obs.scope("fluid.notify"):
        # Each stage decides who emits (suppression/coalescing window) and
        # *when* the payload lands: NP/ENP after the end-to-end RTT, FNCC
        # from the marking hop's position on the return path.  The delay
        # line is sized >= max(rtt)+1 (see delay_depth), so the modulo is a
        # ring-buffer index, never an aliased (shortened) feedback delay.
        # ``emit`` is a [F] float emission intensity (exact 0/1 hard,
        # fractional soft) — it is also the per-step control-traffic
        # counter surfaced in the trace below.
        (emit, np_tmr, wslot), cc_notif = cc.dispatch(
            cc.NOTIFICATION, par.notif_code, par.notif,
            cc.NotifCtx(marked=mark_lvl, mark_fh=mark_fh, np_tmr_t=np_tmr_t,
                        hops=hops, rtt=sd.rtt, t=st.t, D=D, tau=tau),
            st.cc)
        rslot = st.t % D
        if fused:
            # branch-free ring ops: one-hot compare instead of scatters.
            # Exact: each (wslot[f], f) cell gets the same single add/set,
            # every other cell an exact +0.0 / keep; the read row rslot is
            # disjoint from all write slots (0 < rtt < D).
            d_iota = jnp.arange(D, dtype=jnp.int32)[:, None]       # [D, 1]
            w_hot = d_iota == wslot[None, :]                       # [D, F]
            trig_buf = st.trig_buf + jnp.where(w_hot, emit[None, :], 0.0)
            tgt_buf = soft.select(
                tau,
                jnp.where(w_hot,
                          emit[None, :] * tgt[None, :]
                          + (1.0 - emit[None, :]) * st.tgt_buf,
                          st.tgt_buf),
                jnp.where(w_hot & (emit[None, :] > 0), tgt[None, :],
                          st.tgt_buf))
            cnp = soft.select(tau, jnp.minimum(trig_buf[rslot], 1.0),
                              (trig_buf[rslot] > 0).astype(jnp.float32))
            tgt_rx = tgt_buf[rslot]
            trig_buf = jnp.where(d_iota == rslot, 0.0, trig_buf)
        else:
            trig_buf = st.trig_buf.at[wslot, fidx].add(emit)
            prev_tgt = st.tgt_buf[wslot, fidx]
            tgt_buf = st.tgt_buf.at[wslot, fidx].set(
                soft.select(tau,
                            emit * tgt + (1.0 - emit) * prev_tgt,
                            jnp.where(emit > 0, tgt, prev_tgt)))
            cnp = soft.select(tau, jnp.minimum(trig_buf[rslot], 1.0),
                              (trig_buf[rslot] > 0).astype(jnp.float32))
            tgt_rx = tgt_buf[rslot]
            trig_buf = trig_buf.at[rslot].set(0.0)

    # ---- 6. reaction (cc.REACTION dispatch), branchless -------------------
    with obs.scope("fluid.react"):
        # Every registered reaction (fixed-rate PFC source / DCQCN RP / the
        # paper's ERP / delay-target swift / ...) advances from the same
        # context; the traced ``react_code`` selects one.  The queuing-delay
        # estimate (bytes queued along the path / line rate) feeds the
        # mark-free delay-based stages.
        qdelay = fdiv(jnp.sum(jnp.where(holds_queue, qh, 0.0), axis=1),
                      par.line_rate)
        react_out, cc_react = cc.dispatch(
            cc.REACTION, par.react_code, par.react,
            cc.ReactCtx(rate=st.rate, rp_target=st.rp_target, alpha=st.alpha,
                        byte_cnt=st.byte_cnt, tmr=st.tmr,
                        alpha_tmr=st.alpha_tmr, bc_stage=st.bc_stage,
                        t_stage=st.t_stage, hold=st.hold, cnp=cnp,
                        tgt_rx=tgt_rx, qdelay=qdelay, jitter=sd.jitter,
                        gen_rate=sd.gen_rate, line_rate=par.line_rate, dt=dt,
                        tau=tau),
            st.cc)

        new = FluidState(
            qh=qh, nicq=nicq, delivered=delivered, offered=offered,
            dropped=dropped, est=est, paused=paused, rate=react_out.rate,
            rp_target=react_out.rp_target, alpha=react_out.alpha,
            byte_cnt=react_out.byte_cnt, tmr=react_out.tmr,
            alpha_tmr=react_out.alpha_tmr, bc_stage=react_out.bc_stage,
            t_stage=react_out.t_stage, hold=react_out.hold, np_tmr=np_tmr,
            trig_buf=trig_buf, tgt_buf=tgt_buf, path_idx=path_idx,
            cc={**st.cc, **cc_mark, **cc_notif, **cc_react}, t=st.t + 1)

    # ---- trace (the scan folds it into the decimated sample) --------------
    with obs.scope("fluid.decimate"):
        rate = react_out.rate
        trace = StepTrace(
            delivered=delivered, rate=rate, inst_thr=fdiv(deliv_step, dt),
            max_q=jnp.max(B),
            n_paused=jnp.sum((paused > 0.5).astype(jnp.int32)),
            marked=marked, cnp=cnp > 0,
            n_nonmin=jnp.sum((path_idx > 0).astype(jnp.int32)),
            ctrl=emit,
            pause_time=jnp.sum(paused) * dt,
            vc_stall=paused.reshape(L, V).sum(axis=0) * dt)
    return new, trace


def make_step_fn(scn: Scenario, cfg: "CCConfig | CCSpec",
                 delay_slots: int | None = None, *,
                 reduce: str = "fused", dense_rows: int | None = None,
                 temperature: float = 0.0):
    """Returns step(state) -> (state, StepTrace). Pure; closes over statics.

    ``delay_slots`` pins a fixed delay-line depth (legacy callers passing
    ``DELAY_SLOTS``); it raises if any flow's RTT would overflow it.  By
    default the depth is sized from the scenario (``delay_depth``).
    ``reduce`` selects the reduction engine (see ``fluid_step``);
    ``dense_rows=None`` lays the dense reduction out from the
    scenario (``dense_layout``), an explicit count pins its rectangle
    (``dense_engine``), 0 forces the segment-sum engine.
    ``temperature`` selects the soft-relaxed dynamics (``repro.tune``).
    """
    if delay_slots is not None:
        _check_delay(scn, delay_slots)
    check_routing_paths(cfg, scn)
    n_vcs = int(getattr(cfg.link, "n_vcs", 1))
    sd = scenario_device(scn, n_vcs=n_vcs)
    par = step_params(cfg, temperature=temperature)
    n_sw = int(scn.n_switches)
    dt = float(cfg.sim.dt)
    rows = 0 if reduce != "fused" else dense_reduce_rows(scn, n_vcs) \
        if dense_rows is None else int(dense_rows)
    dense_blocks, layout = dense_engine(
        [scn], n_vcs, rows, pinned=dense_rows is not None)
    if layout is not None:
        sd = sd._replace(red_idx=layout[0][0], red_back=layout[1][0])

    def step(st: FluidState):
        return fluid_step(st, sd, par, dt=dt, n_switches=n_sw,
                          reduce=reduce, dense_blocks=dense_blocks,
                          n_vcs=n_vcs)

    return step
