"""Composable CC-stage registry: pluggable detection / notification /
reaction riding one jit.

The paper's thesis is that DCQCN's closed loop decomposes into three
independently improvable mechanisms — congestion detection (CP -> ECP),
notification (NP -> ENP) and injection throttling (RP -> ERP).  This
module makes each stage a first-class, sweepable axis: **marking**,
**notification** and **reaction** are registries of small components,
and a ``CCSpec(marking=..., notification=..., reaction=...)`` names one
entry per family.

The protocol (one entry per ``StageRegistry``):

  * ``params``      — ``{field_name: (spec) -> python scalar}``: the
    constants this stage reads, flattened into the family's traced
    param pytree (``StepParams.mark`` / ``.notif`` / ``.react``).
    Field names are namespaced by convention (``cp_kmin``,
    ``erp_settle``); a name shared across stages (``drain_gain``) must
    extract the same value — ``device_params`` raises otherwise.
  * ``init_state``  — optional ``(Scenario) -> {key: [F] array}``:
    per-flow state this stage carries across steps, stacked into
    ``FluidState.cc`` (every registered stage contributes, so the
    pytree is shape-stable across a whole sweep batch).
  * ``step``        — the pure per-``dt`` update
    ``(params, ctx, state) -> (outputs, state_updates)``.  ``ctx`` is
    the family's context NamedTuple below; outputs are selected across
    stages with ``jnp.where`` on the family's traced code, which is
    what lets any (marking x notification x reaction x param grid)
    product compile to ONE ``Sweep`` launch — exactly like
    ``route_code`` for adaptive routing.

Dispatch (``dispatch``) evaluates every registered stage and selects by
the traced integer code — stage selection is *data*, so a grid mixing
stages never recompiles.  Codes are assigned in registration order and
the built-in order is frozen (cp/ecp/slope, np/enp/fncc,
pfc/rp/erp/swift): appending new stages never renumbers existing ones.

Adding a variant (three lines + the step function)::

    from repro.core import cc

    def _mark_mine(p, ctx, state):
        base = ((ctx.B1_w > p["mine_thresh"]) & ctx.present
                & ctx.holds_queue).astype(jnp.float32)
        return (base, ctx.grant_next), {}

(mark intensities are floats — exact 0/1 for a hard stage; a stage may
also smooth its gates behind ``ctx.tau``, see ``repro.tune.soft`` and
the built-ins below, so ``jax.grad`` flows through the dt-scan at
``temperature > 0``)

    cc.MARKING.register("mine",
        params={"mine_thresh": lambda s: s.dcqcn.kmin}, step=_mark_mine)

then ``CCSpec(marking="mine")`` sweeps it against every other axis.

Built-in stages
---------------
marking:
  * ``cp``    — step marking on occupancy only (DCQCN's CP).
  * ``ecp``   — occupancy AND the flow's arrival rate above its
    waterfilled fair grant (the paper's ECP; victims never marked).
  * ``slope`` — RED-style ramp: marking probability rises from 0 at
    ``kmin`` to ``pmax`` at ``kmax`` (finally exercising
    ``DCQCNParams.pmax``); the probability is realised *deterministically*
    by per-flow error diffusion (an accumulator fires when it crosses 1),
    keeping the fluid model reproducible.
notification:
  * ``np``    — DCQCN NP: one CNP per ``cnp_window``, delivered after
    the full end-to-end RTT.
  * ``enp``   — the paper's ENP: fast coalescing + severity payload,
    still end-to-end.
  * ``fncc``  — FNCC-style in-path notification: the congested hop
    writes the severity payload directly into the return path, so the
    feedback delay shrinks to the upstream trip from the marking hop
    (``rtt/2 * (h_mark+1)/hops``, scaled by ``fncc.rtt_scale``).
reaction:
  * ``pfc``   — fixed-rate source (no end-to-end CC; PFC only).
  * ``rp``    — DCQCN RP (alpha EWMA + staged byte/timer recovery).
  * ``erp``   — the paper's ERP (settle to signalled fair share, hold,
    desynchronised additive recovery).
  * ``swift`` — delay-target reaction (Swift-like): throttles on the
    queuing-delay *estimate* (bytes queued along the path / line rate)
    instead of mark arrival — multiplicative decrease proportional to
    the excess over ``swift.target_delay`` at most once per guard
    period, additive recovery below target.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.tune import soft


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Stage:
    """One registered component of a family (see module docstring)."""

    family: str
    name: str
    code: int
    params: dict                      # {field: (spec) -> python scalar}
    step: Callable                    # (params, ctx, state) -> (out, upd)
    int_params: frozenset = frozenset()   # fields traced as int32
    init_state: Callable | None = None
    # reaction stages only: does this stage read the mark/CNP feedback?
    # Mark-free reactions (swift's delay signal) make the marking axis
    # dead — ablation grids cross it only for consumers.
    consumes_marks: bool = True


class StageRegistry:
    """Ordered name -> Stage mapping; codes follow registration order."""

    def __init__(self, family: str):
        self.family = family
        self._stages: dict[str, Stage] = {}

    def register(self, name: str, *, step: Callable,
                 params: dict | None = None,
                 int_params: tuple = (),
                 init_state: Callable | None = None,
                 consumes_marks: bool = True) -> Stage:
        if name in self._stages:
            raise ValueError(
                f"{self.family} stage {name!r} already registered")
        stage = Stage(family=self.family, name=name,
                      code=len(self._stages), params=dict(params or {}),
                      int_params=frozenset(int_params),
                      step=step, init_state=init_state,
                      consumes_marks=consumes_marks)
        self._stages[name] = stage
        return stage

    def __contains__(self, name: str) -> bool:
        return name in self._stages

    def names(self) -> tuple[str, ...]:
        return tuple(self._stages)

    def get(self, name: str) -> Stage:
        if name not in self._stages:
            raise KeyError(
                f"unknown {self.family} stage {name!r}; registered: "
                f"{self.names()}")
        return self._stages[name]

    def code(self, name: str) -> int:
        return self.get(name).code

    def stages(self) -> tuple[Stage, ...]:
        return tuple(self._stages.values())

    def device_params(self, spec) -> dict:
        """Union of every registered stage's traced param scalars.

        Every field traces as float32 unless its stage listed it in
        ``int_params`` (int32) — dtype is a declaration, not inferred
        from the python value, so ``SwiftParams(ai=10**12)`` and
        ``ai=1e12`` build the identical pytree.  A field name declared
        by several stages must extract the same value (shared constants
        like ``drain_gain``); a mismatch raises.
        """
        out: dict = {}
        for stage in self.stages():
            for field, fn in stage.params.items():
                v = fn(spec)
                is_int = field in stage.int_params
                if field in out:
                    if out[field] != (v, is_int):
                        raise ValueError(
                            f"{self.family} param {field!r} extracted "
                            f"conflicting values {out[field][0]!r} vs "
                            f"{v!r}; shared field names must share "
                            f"semantics — namespace stage-specific "
                            f"params by the stage name")
                    continue
                out[field] = (v, is_int)
        return {field: jnp.asarray(v, jnp.int32 if is_int
                                   else jnp.float32)
                for field, (v, is_int) in out.items()}

    def init_cc_state(self, scn) -> dict:
        """Every registered stage's per-flow state for one scenario."""
        out: dict = {}
        for stage in self.stages():
            if stage.init_state is None:
                continue
            for k, v in stage.init_state(scn).items():
                if k in out:
                    raise ValueError(
                        f"{self.family} state key {k!r} declared twice; "
                        f"namespace state keys by the stage name")
                out[k] = jnp.asarray(v)
        return out


MARKING = StageRegistry("marking")
NOTIFICATION = StageRegistry("notification")
REACTION = StageRegistry("reaction")

FAMILIES = (MARKING, NOTIFICATION, REACTION)


def init_cc_state(scn) -> dict:
    """Union of all families' per-flow stage state for one scenario."""
    out: dict = {}
    for reg in FAMILIES:
        for k, v in reg.init_cc_state(scn).items():
            if k in out:
                raise ValueError(f"cc state key {k!r} declared by two "
                                 f"families")
            out[k] = v
    return out


def _select(code, outs):
    """where-chain over same-structure pytrees, stage 0 as the base."""
    sel = outs[0]
    for i, o in enumerate(outs[1:], start=1):
        sel = jax.tree.map(lambda a, b, i=i: jnp.where(code == i, b, a),
                           sel, o)
    return sel


def dispatch(registry: StageRegistry, code, params: dict, ctx,
             state: dict):
    """Evaluate every stage of ``registry`` and select by traced code.

    Returns ``(outputs, family_state)`` where ``family_state`` maps
    every state key any stage of this family owns to its post-step
    value (non-selected stages pass their keys through unchanged, so
    merging families back into ``FluidState.cc`` is a dict union).
    """
    outs = []
    owned: set[str] = set()
    for stage in registry.stages():
        main, upd = stage.step(params, ctx, state)
        owned.update(upd)
        outs.append((main, upd))
    full = []
    for main, upd in outs:
        merged = {k: state[k] for k in owned}
        merged.update(upd)
        full.append((main, merged))
    return _select(code, full)


# ---------------------------------------------------------------------------
# family contexts
# ---------------------------------------------------------------------------


class MarkCtx(NamedTuple):
    """Phase-4 context: per-(flow, hop) congestion signals.

    ``B1_w``: occupancy of each hop's sink queue — under multiple
    virtual channels (``LinkParams.n_vcs > 1``) this is the flow's own
    (wire, VC) lane, so marking never charges a flow for a sibling
    VC's backlog; ``present``: the flow has bytes there;
    ``holds_queue``: hop owns a queue (not the delivery hop);
    ``dem_next``/``grant_next``/``over_next``: the flow's demand,
    waterfilled fair grant and oversubscription flag at its *requested
    output* wire (per-wire notions: grants share the wire's capacity
    across all its VCs).
    """

    B1_w: jnp.ndarray         # [F, H] f32
    present: jnp.ndarray      # [F, H] bool
    holds_queue: jnp.ndarray  # [F, H] bool
    dem_next: jnp.ndarray     # [F, H] f32
    grant_next: jnp.ndarray   # [F, H] f32
    over_next: jnp.ndarray    # [F, H] f32 (exact 0/1 hard, graded soft)
    port_buffer: jnp.ndarray  # [] f32
    line_rate: jnp.ndarray    # [] f32
    tau: jnp.ndarray          # [] f32 soft-relaxation temperature


class NotifCtx(NamedTuple):
    """Phase-5 context: who marked, and the delay-line geometry."""

    marked: jnp.ndarray       # [F] f32 mark level (exact 0/1 hard)
    mark_fh: jnp.ndarray      # [F, H] f32 — which hop(s), graded soft
    np_tmr_t: jnp.ndarray     # [F] f32 — suppression timer (post-tick)
    hops: jnp.ndarray         # [F] int32 — current path's hop count
    rtt: jnp.ndarray          # [F] int32 — end-to-end delay in dt steps
    t: jnp.ndarray            # [] int32 — step counter
    D: int                    # static delay-line depth
    tau: jnp.ndarray          # [] f32 soft-relaxation temperature


class ReactCtx(NamedTuple):
    """Phase-6 context: reaction-point state + feedback signals."""

    rate: jnp.ndarray         # [F] f32
    rp_target: jnp.ndarray    # [F]
    alpha: jnp.ndarray        # [F]
    byte_cnt: jnp.ndarray     # [F]
    tmr: jnp.ndarray          # [F]
    alpha_tmr: jnp.ndarray    # [F]
    bc_stage: jnp.ndarray     # [F] int32
    t_stage: jnp.ndarray      # [F] int32
    hold: jnp.ndarray         # [F]
    cnp: jnp.ndarray          # [F] f32 — notification level (0/1 hard)
    tgt_rx: jnp.ndarray       # [F] f32 — received severity payload
    qdelay: jnp.ndarray       # [F] f32 — queuing-delay estimate (s)
    jitter: jnp.ndarray       # [F] f32 — deterministic per-flow jitter
    gen_rate: jnp.ndarray     # [F] f32 — offered rate (pfc source)
    line_rate: jnp.ndarray    # [] f32
    dt: jnp.ndarray           # [] f32
    tau: jnp.ndarray          # [] f32 soft-relaxation temperature


class ReactOut(NamedTuple):
    """Reaction-point state after one dt (fields a stage does not own
    pass through from the context)."""

    rate: jnp.ndarray
    rp_target: jnp.ndarray
    alpha: jnp.ndarray
    byte_cnt: jnp.ndarray
    tmr: jnp.ndarray
    alpha_tmr: jnp.ndarray
    bc_stage: jnp.ndarray
    t_stage: jnp.ndarray
    hold: jnp.ndarray


def _passthrough(ctx: ReactCtx) -> ReactOut:
    return ReactOut(rate=ctx.rate, rp_target=ctx.rp_target,
                    alpha=ctx.alpha, byte_cnt=ctx.byte_cnt, tmr=ctx.tmr,
                    alpha_tmr=ctx.alpha_tmr, bc_stage=ctx.bc_stage,
                    t_stage=ctx.t_stage, hold=ctx.hold)


# ---------------------------------------------------------------------------
# marking stages
# ---------------------------------------------------------------------------


def _mark_common(thresh, ctx: MarkCtx):
    """(base mark intensity, queue excess over thresh) shared by variants.

    The intensity is an exact 0/1 float in hard mode (``tau == 0``
    selects the original boolean, cast); under the soft model the
    threshold crossing becomes a sigmoid in the occupancy — this is the
    site that gives kmin/detect-threshold a gradient.  The presence
    gates stay hard multipliers (state-dependent, not tuned; keeping
    them exact prevents ghost marks at empty queues).
    """
    gate_h = ((ctx.B1_w > thresh) & ctx.present
              & ctx.holds_queue).astype(jnp.float32)
    gate_s = (soft.unit_gate(ctx.B1_w - thresh, ctx.tau, ctx.port_buffer)
              * ctx.present * ctx.holds_queue)
    base = soft.select(ctx.tau, gate_s, gate_h)
    qexc = jnp.clip((ctx.B1_w - thresh) / ctx.port_buffer, 0.0, 1.0)
    return base, qexc


def _severity(ctx: MarkCtx, drain_gain, qexc):
    """``grant_next * (1 - drain_gain * qexc)``, inf-sentinel safe.

    Hops without a finite fair grant keep the exact ``inf`` payload the
    hard min-severity aggregation expects, but never on a product: a
    literal ``inf * (1 - g*qexc)`` would hand ``jax.grad`` an infinite
    partial, and even a zero cotangent times inf is nan.  Finite
    entries are bitwise the plain product.
    """
    finite = jnp.isfinite(ctx.grant_next)
    g_fin = jnp.where(finite, ctx.grant_next, 0.0)
    return jnp.where(finite, g_fin * (1.0 - drain_gain * qexc), jnp.inf)


def _mark_cp(p, ctx: MarkCtx, state):
    base, qexc = _mark_common(p["cp_kmin"], ctx)
    sev = _severity(ctx, p["drain_gain"], qexc)
    return (base, sev), {}


def _mark_ecp(p, ctx: MarkCtx, state):
    base, qexc = _mark_common(p["ecp_thresh"], ctx)
    # hard: oversubscribed output AND demand above the slack-scaled
    # fair grant; soft: product of the graded oversubscription level
    # and a sigmoid in the demand excess (grant_next's inf sentinels
    # drive the sigmoid argument to -inf -> exactly 0, never nan).
    cong_h = ((ctx.over_next > 0)
              & (ctx.dem_next > p["ecp_slack"] * ctx.grant_next)
              ).astype(jnp.float32)
    cong_s = ctx.over_next * soft.unit_gate(
        ctx.dem_next - p["ecp_slack"] * ctx.grant_next, ctx.tau,
        ctx.line_rate)
    congesting = soft.select(ctx.tau, cong_s, cong_h)
    sev = _severity(ctx, p["drain_gain"], qexc)
    return (base * congesting, sev), {}


def _mark_slope(p, ctx: MarkCtx, state):
    """RED-style kmin..kmax ramp, realised by per-flow error diffusion.

    The marking probability ``p(B)`` (0 below kmin, ``pmax`` ramp to
    kmax, 1 above) accumulates per flow; a mark fires when the
    accumulator crosses 1 and spends it — a deterministic thinning with
    exactly the right long-run marking rate, which keeps the fluid
    model reproducible (no RNG in the hot loop).  The soft model fires
    fractionally (sigmoid in the accumulator excess) and spends what it
    fired, so the long-run rate is preserved while kmin/kmax/pmax all
    get gradients through the ramp.
    """
    kmin, kmax = p["slope_kmin"], p["slope_kmax"]
    base, qexc = _mark_common(kmin, ctx)
    ramp = jnp.clip((ctx.B1_w - kmin) / jnp.maximum(kmax - kmin, 1.0),
                    0.0, 1.0)
    prob_fh = jnp.where(ctx.B1_w >= kmax, 1.0, p["slope_pmax"] * ramp)
    prob_fh = prob_fh * base
    prob = jnp.max(prob_fh, axis=1)                    # [F]
    acc = state["slope_acc"] + prob
    fire_h = acc >= 1.0
    fire = soft.select(ctx.tau,
                       soft.unit_gate(acc - 1.0, ctx.tau, 1.0),
                       fire_h.astype(jnp.float32))
    acc = soft.select(ctx.tau, acc - fire,
                      jnp.where(fire_h, acc - 1.0, acc))
    sev = _severity(ctx, p["drain_gain"], qexc)
    return (base * fire[:, None], sev), {"slope_acc": acc}


# ---------------------------------------------------------------------------
# notification stages
# ---------------------------------------------------------------------------


def _notify_window(window, ctx: NotifCtx):
    """Suppression window shared by NP/ENP/FNCC.

    Returns the [F] emission intensity (exact 0/1 hard; soft = mark
    level x a sigmoid timer gate) and the partially-reset suppression
    timer (a full emission resets it to 0, a fractional one
    proportionally — annealing recovers the hard reset).
    """
    emit_h = ((ctx.marked > 0)
              & (ctx.np_tmr_t >= window)).astype(jnp.float32)
    np_h = jnp.where(emit_h > 0, 0.0, ctx.np_tmr_t)
    emit_s = ctx.marked * soft.unit_gate(ctx.np_tmr_t - window, ctx.tau,
                                         window)
    emit = soft.select(ctx.tau, emit_s, emit_h)
    np_tmr = soft.select(ctx.tau, (1.0 - emit_s) * ctx.np_tmr_t, np_h)
    return emit, np_tmr


def _notif_np(p, ctx: NotifCtx, state):
    emit, np_tmr = _notify_window(p["np_window"], ctx)
    wslot = (ctx.t + ctx.rtt) % ctx.D
    return (emit, np_tmr, wslot), {}


def _notif_enp(p, ctx: NotifCtx, state):
    emit, np_tmr = _notify_window(p["enp_window"], ctx)
    wslot = (ctx.t + ctx.rtt) % ctx.D
    return (emit, np_tmr, wslot), {}


def _notif_fncc(p, ctx: NotifCtx, state):
    """In-path notification: the marking hop writes the return path.

    The payload skips the remaining forward trip and the destination
    turnaround — it only rides upstream from the first marking hop, so
    the delay is the hop-proportional share of the one-way latency,
    ``rtt/2 * (h_mark+1)/hops`` (clipped to [2, rtt]: never same-step,
    never slower than the end-to-end CNP).
    """
    emit, np_tmr = _notify_window(p["fncc_window"], ctx)
    h_mark = jnp.argmax(ctx.mark_fh, axis=1).astype(jnp.float32)
    frac = (h_mark + 1.0) / jnp.maximum(ctx.hops.astype(jnp.float32), 1.0)
    rtt_f = ctx.rtt.astype(jnp.float32)
    rtt_eff = jnp.round(rtt_f * 0.5 * frac * p["fncc_scale"])
    rtt_eff = jnp.clip(rtt_eff.astype(jnp.int32), 2, ctx.rtt)
    wslot = (ctx.t + rtt_eff) % ctx.D
    return (emit, np_tmr, wslot), {}


# ---------------------------------------------------------------------------
# reaction stages
# ---------------------------------------------------------------------------


def _react_pfc(p, ctx: ReactCtx, state):
    out = _passthrough(ctx)._replace(
        rate=jnp.minimum(ctx.gen_rate, ctx.line_rate))
    return out, {}


def _react_rp(p, ctx: ReactCtx, state):
    """DCQCN RP: alpha EWMA + staged byte/timer recovery machine.

    Soft path: every CNP-gated update blends by the fractional
    notification level (``soft.pick``), so the rate cut, alpha EWMA and
    counter resets carry gradients to rdf/g and — through the marking
    intensity upstream — to the detection thresholds; the integer
    stage machine and its byte/timer events stay hard (discrete
    counters have no useful relaxation), but rai/rhai still get exact
    gradients because they enter the fired updates linearly.
    """
    g = p["rp_g"]
    dt, tau = ctx.dt, ctx.tau
    c = ctx.cnp                      # [F] level: exact 0/1 in hard mode
    cnp = ctx.cnp > 0
    pk = lambda a, b: soft.pick(tau, c, cnp, a, b)   # noqa: E731
    alpha_tmr = ctx.alpha_tmr + dt
    a_tick = alpha_tmr >= p["rp_timer"]
    alpha = jnp.where(a_tick, (1 - g) * ctx.alpha, ctx.alpha)
    alpha_tmr = jnp.where(a_tick, 0.0, alpha_tmr)
    rp_target = pk(ctx.rate, ctx.rp_target)
    rate = pk(ctx.rate * (1 - alpha * p["rp_rdf"]), ctx.rate)
    alpha = pk((1 - g) * alpha + g, alpha)
    byte_cnt = pk(0.0, ctx.byte_cnt + ctx.rate * dt)
    tmr = pk(0.0, ctx.tmr + dt)
    alpha_tmr = pk(0.0, alpha_tmr)
    bc_stage = jnp.where(cnp, 0, ctx.bc_stage)
    t_stage = jnp.where(cnp, 0, ctx.t_stage)
    b_ev = byte_cnt >= p["rp_byte"]
    t_ev = tmr >= p["rp_timer"]
    byte_cnt = jnp.where(b_ev, 0.0, byte_cnt)
    tmr = jnp.where(t_ev, 0.0, tmr)
    bc_stage = bc_stage + b_ev.astype(jnp.int32)
    t_stage = t_stage + t_ev.astype(jnp.int32)
    ev = b_ev | t_ev
    imax = jnp.maximum(bc_stage, t_stage)
    imin = jnp.minimum(bc_stage, t_stage)
    in_fr = imax <= p["rp_fr_stages"]
    in_hyper = imin > p["rp_fr_stages"]
    rp_target = jnp.where(ev & ~in_fr & ~in_hyper, rp_target + p["rp_rai"],
                          rp_target)
    rp_target = jnp.where(
        ev & in_hyper,
        rp_target + p["rp_rhai"]
        * (imin - p["rp_fr_stages"]).astype(jnp.float32),
        rp_target)
    rate = jnp.where(ev, 0.5 * (rate + rp_target), rate)
    rate = soft.clip(rate, p["rp_min_rate"], ctx.line_rate, tau,
                     ctx.line_rate)
    rp_target = soft.clip(rp_target, p["rp_min_rate"], ctx.line_rate,
                          tau, ctx.line_rate)
    out = _passthrough(ctx)._replace(
        rate=rate, rp_target=rp_target, alpha=alpha, byte_cnt=byte_cnt,
        tmr=tmr, alpha_tmr=alpha_tmr, bc_stage=bc_stage, t_stage=t_stage)
    return out, {}


def _react_erp(p, ctx: ReactCtx, state):
    """ERP: settle to signalled fair share, hold, additive recovery.

    Soft path: settle/hold blend by the notification level, and the
    hold-down expiry becomes a sigmoid recovery gate — erp_settle,
    erp_hold and erp_rai all differentiable through the scan.
    """
    dt, tau = ctx.dt, ctx.tau
    c = ctx.cnp
    cnp = ctx.cnp > 0
    pk = lambda a, b: soft.pick(tau, c, cnp, a, b)   # noqa: E731
    settle = jnp.maximum(p["erp_settle"] * ctx.tgt_rx, p["erp_min_rate"])
    rate = pk(settle, ctx.rate)
    hold = pk(p["erp_hold"], jnp.maximum(ctx.hold - dt, 0.0))
    # per-flow desynchronised recovery slope (deterministic jitter)
    slope = p["erp_rai"] * (1.0 + p["erp_jitter"] * ctx.jitter) * dt
    rec_s = (1.0 - c) * soft.unit_gate(-hold, tau, p["erp_hold"] + 1e-9)
    rate = soft.select(tau, rate + rec_s * slope,
                       jnp.where(~cnp & (hold <= 0), rate + slope, rate))
    rate = soft.clip(rate, p["erp_min_rate"], ctx.line_rate, tau,
                     ctx.line_rate)
    return _passthrough(ctx)._replace(rate=rate, hold=hold), {}


def _react_swift(p, ctx: ReactCtx, state):
    """Delay-target throttling on the path queuing-delay estimate.

    Hard path = ``swift_update_ref`` verbatim (the single definition,
    in ``repro.kernels.ref``).  Soft path: the over-target and
    cool-down gates become sigmoids, blending the multiplicative
    decrease against the additive recovery — target_delay/beta/ai get
    gradients (the qdelay signal itself is already differentiable).
    """
    from repro.kernels.ref import swift_update_ref
    rate_h, cool_h = swift_update_ref(
        ctx.rate, state["swift_cool"], ctx.qdelay,
        target=p["swift_target"], beta=p["swift_beta"], ai=p["swift_ai"],
        guard=p["swift_guard"], min_rate=p["swift_min_rate"],
        line_rate=ctx.line_rate, dt=ctx.dt)
    tau = ctx.tau
    target, beta = p["swift_target"], p["swift_beta"]
    cool = jnp.maximum(state["swift_cool"] - ctx.dt, 0.0)
    g_over = soft.unit_gate(ctx.qdelay - target, tau, target + 1e-12)
    g_can = soft.unit_gate(-cool, tau, p["swift_guard"] + 1e-12)
    factor = 1.0 - beta * (ctx.qdelay - target) \
        / jnp.maximum(ctx.qdelay, 1e-12)
    dec = jnp.maximum(ctx.rate * jnp.maximum(factor, 1.0 - beta),
                      p["swift_min_rate"])
    cut = g_over * g_can
    rate_s = cut * dec + (1.0 - cut) * \
        (ctx.rate + (1.0 - g_over) * p["swift_ai"] * ctx.dt)
    rate_s = soft.clip(rate_s, p["swift_min_rate"], ctx.line_rate, tau,
                       ctx.line_rate)
    cool_s = cut * p["swift_guard"] + (1.0 - cut) * cool
    rate = soft.select(tau, rate_s, rate_h)
    cool = soft.select(tau, cool_s, cool_h)
    return _passthrough(ctx)._replace(rate=rate), {"swift_cool": cool}


# ---------------------------------------------------------------------------
# built-in registration (codes frozen in this order)
# ---------------------------------------------------------------------------


def _zeros_f(scn) -> np.ndarray:
    return np.zeros((scn.routes.shape[0],), np.float32)


MARKING.register(
    "cp", step=_mark_cp,
    params={"cp_kmin": lambda s: s.dcqcn.kmin,
            "drain_gain": lambda s: s.rev.erp_drain_gain})
MARKING.register(
    "ecp", step=_mark_ecp,
    params={"ecp_thresh": lambda s: s.rev.detect_threshold,
            "ecp_slack": lambda s: s.rev.ecp_fairness_slack,
            "drain_gain": lambda s: s.rev.erp_drain_gain})
MARKING.register(
    "slope", step=_mark_slope,
    params={"slope_kmin": lambda s: s.dcqcn.kmin,
            "slope_kmax": lambda s: s.dcqcn.kmax,
            "slope_pmax": lambda s: s.dcqcn.pmax,
            "drain_gain": lambda s: s.rev.erp_drain_gain},
    init_state=lambda scn: {"slope_acc": _zeros_f(scn)})

NOTIFICATION.register(
    "np", step=_notif_np,
    params={"np_window": lambda s: s.dcqcn.cnp_window})
NOTIFICATION.register(
    "enp", step=_notif_enp,
    params={"enp_window": lambda s: s.rev.enp_coalesce})
NOTIFICATION.register(
    "fncc", step=_notif_fncc,
    params={"fncc_window": lambda s: s.fncc.coalesce,
            "fncc_scale": lambda s: s.fncc.rtt_scale})

REACTION.register("pfc", step=_react_pfc, consumes_marks=False)
REACTION.register(
    "rp", step=_react_rp,
    params={"rp_g": lambda s: s.dcqcn.g,
            "rp_rdf": lambda s: s.dcqcn.rate_decrease_factor,
            "rp_timer": lambda s: s.dcqcn.timer_T,
            "rp_byte": lambda s: s.dcqcn.byte_counter_B,
            "rp_rai": lambda s: s.dcqcn.rai,
            "rp_rhai": lambda s: s.dcqcn.rhai,
            "rp_fr_stages": lambda s: s.dcqcn.fr_stages,
            "rp_min_rate": lambda s: s.dcqcn.min_rate},
    int_params=("rp_fr_stages",))
REACTION.register(
    "erp", step=_react_erp,
    params={"erp_settle": lambda s: s.rev.erp_settle,
            "erp_rai": lambda s: s.rev.erp_rai,
            "erp_jitter": lambda s: s.rev.erp_jitter,
            "erp_hold": lambda s: s.rev.erp_hold,
            "erp_min_rate": lambda s: s.rev.min_rate})
REACTION.register(
    "swift", step=_react_swift, consumes_marks=False,
    params={"swift_target": lambda s: s.swift.target_delay,
            "swift_beta": lambda s: s.swift.beta,
            "swift_ai": lambda s: s.swift.ai,
            "swift_guard": lambda s: s.swift.guard,
            "swift_min_rate": lambda s: s.swift.min_rate},
    init_state=lambda scn: {"swift_cool": _zeros_f(scn)})
