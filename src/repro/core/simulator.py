"""Scan driver + result analysis for the CC fluid model.

``run`` advances one (scenario, config) point; the scan body decimates
traces on device (one ``TraceSample`` per ``trace_every`` steps), so the
trace memory pulled to host shrinks by that factor.  Batched sweeps live
in ``experiments.py`` and share the same scan body.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import obs
from .fluid import (FluidState, Scenario, StepTrace, init_state,
                    make_step_fn)
from .params import CCConfig, CCScheme


class TraceSample(StepTrace):
    """One decimated trace sample covering ``trace_every`` sim steps.

    Cumulative fields (``delivered``, ``rate``) are the window's last
    step, i.e. a strided sample of the full trace; ``inst_thr`` is the
    window-mean delivery rate; ``max_q`` / ``n_paused`` / ``n_nonmin``
    are window maxima; ``marked`` / ``cnp`` are window event *counts*
    (so sums over the decimated trace equal sums over the full one);
    ``ctrl`` is the window *sum* of notification emissions — a float,
    because the soft model (``StepParams.temperature > 0``) emits
    fractional control traffic.  ``pause_time`` / ``vc_stall`` are
    window *sums* of pause wire-seconds (total / per VC), so run totals
    are decimation-invariant too.
    """


def _zero_accum(st: FluidState, n_vcs: int = 1):
    # shapes follow the state so the same scan body serves single runs
    # ([] / [F]) and batched sweeps ([R] / [R, F]).  ``n_vcs`` is passed
    # explicitly: the [V] per-VC stall accumulator cannot be told apart
    # from the flat [L * V] pause vector by shape alone.
    return (jnp.zeros_like(st.t, jnp.float32),    # max_q
            jnp.zeros_like(st.t, jnp.int32),      # n_paused
            jnp.zeros_like(st.nicq, jnp.int32),   # marked
            jnp.zeros_like(st.nicq, jnp.int32),   # cnp
            jnp.zeros_like(st.t, jnp.int32),      # n_nonmin
            jnp.zeros_like(st.nicq, jnp.float32),  # ctrl
            jnp.zeros_like(st.t, jnp.float32),    # pause_time
            jnp.zeros(st.t.shape + (n_vcs,), jnp.float32))  # vc_stall


def _acc_update(acc, tr: StepTrace):
    """Fold one step's trace into the window accumulators.

    Shared by the host-side decimating scan AND the megakernel's
    in-kernel dt-scan (``repro.kernels.fluid_step.megastep_block``) —
    the single definition is what keeps the two trace paths bitwise
    identical."""
    mq, npz, mk, cn, nm, ct, pt, vs = acc
    with obs.scope("fluid.decimate"):
        return (jnp.maximum(mq, tr.max_q),
                jnp.maximum(npz, tr.n_paused),
                mk + tr.marked.astype(jnp.int32),
                cn + tr.cnp.astype(jnp.int32),
                jnp.maximum(nm, tr.n_nonmin),
                ct + tr.ctrl,
                pt + tr.pause_time,
                vs + tr.vc_stall)


def _window_sample(st: FluidState, d0, acc, trace_every: int,
                   dt: float) -> TraceSample:
    """One TraceSample from the window-end state + accumulators."""
    mq, npz, mk, cn, nm, ct, pt, vs = acc
    with obs.scope("fluid.decimate"):
        return TraceSample(
            delivered=st.delivered, rate=st.rate,
            inst_thr=(st.delivered - d0) / jnp.float32(trace_every * dt),
            max_q=mq, n_paused=npz, marked=mk, cnp=cn, n_nonmin=nm,
            ctrl=ct, pause_time=pt, vc_stall=vs)


def decimating_scan(step, st: FluidState, n_samples: int,
                    trace_every: int, dt: float, n_vcs: int = 1, *,
                    block_fn=None):
    """Run ``n_samples * trace_every`` steps, emitting one TraceSample
    per ``trace_every`` steps.  Accumulation happens inside the scan, so
    the full-resolution trace never materialises.

    ``block_fn`` replaces the inner per-step scan with one call per
    trace window (``block_fn(state) -> (state, TraceSample)``) — the
    megakernel's whole-window launch; the outer scan then just chains
    windows.  ``step``/``trace_every``/``dt``/``n_vcs`` are unused in
    that form (the block closes over them)."""
    if block_fn is not None:
        return jax.lax.scan(lambda s, _: block_fn(s), st, None,
                            length=n_samples)

    def outer(st, _):
        d0 = st.delivered

        def inner(carry, _):
            stt = carry[0]
            st2, tr = step(stt)
            return (st2,) + _acc_update(carry[1:], tr), None

        (st, *acc), _ = jax.lax.scan(
            inner, (st,) + _zero_accum(st, n_vcs), None,
            length=trace_every)
        return st, _window_sample(st, d0, tuple(acc), trace_every, dt)

    return jax.lax.scan(outer, st, None, length=n_samples)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _run_scan(state: FluidState, step_fn, n_samples: int,
              trace_every: int, dt: float, n_vcs: int = 1):
    return decimating_scan(step_fn, state, n_samples, trace_every, dt,
                           n_vcs)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _run_block_scan(state: FluidState, block_fn, n_samples: int):
    return decimating_scan(None, state, n_samples, 0, 0.0,
                           block_fn=block_fn)


def make_block_fn(scn: Scenario, cfg: CCConfig, trace_every: int, *,
                  reduce: str = "fused", dense_rows: int | None = None,
                  interpret: bool = False):
    """Megakernel analogue of ``make_step_fn``: one whole trace window
    per launch.

    Returns ``block(state) -> (state, TraceSample)`` running
    ``trace_every`` substeps inside a single ``pallas_call`` with the
    fluid state VMEM-resident throughout (see
    ``repro.kernels.fluid_step.megastep_block``); only the decimated
    sample row leaves the kernel.  The accumulation functions are the
    exact ones ``decimating_scan`` uses, so traces are bit-identical to
    the per-step path.
    """
    from .fluid import (check_routing_paths, dense_engine,
                        dense_reduce_rows, scenario_device, step_body_fn,
                        step_params)
    from repro.kernels.fluid_step import megastep_block
    check_routing_paths(cfg, scn)
    n_vcs = int(getattr(cfg.link, "n_vcs", 1))
    sd = scenario_device(scn, n_vcs=n_vcs)
    par = step_params(cfg)
    dt = float(cfg.sim.dt)
    rows = 0 if reduce != "fused" else dense_reduce_rows(scn, n_vcs) \
        if dense_rows is None else int(dense_rows)
    dense_blocks, _ = dense_engine([scn], n_vcs, rows, pinned=True,
                                   mega=True)
    body = step_body_fn(dt=dt, n_switches=int(scn.n_switches),
                        reduce=reduce, dense_blocks=dense_blocks,
                        n_vcs=n_vcs)

    def block(st: FluidState):
        return megastep_block(
            st, sd, par, body=body, n_substeps=trace_every,
            acc_init=_zero_accum, acc_update=_acc_update,
            make_sample=_window_sample, n_vcs=n_vcs, dt=dt,
            interpret=interpret)

    return block


def _resolve_steps(cfg: CCConfig, n_steps: int | None,
                   trace_every: int | None) -> tuple[int, int]:
    if n_steps is None:
        n_steps = int(round(cfg.sim.t_end / cfg.sim.dt))
    k = cfg.sim.trace_every if trace_every is None else trace_every
    k = max(1, int(k))
    n_samples = -(-n_steps // k)          # ceil: round the run up to a
    return n_samples, k                   # whole number of samples


@dataclasses.dataclass
class SimResult:
    """Host-side view of a finished run.

    Trace arrays are decimated by ``trace_every`` (see TraceSample for
    the per-field semantics); ``times`` marks each sample's window end.
    """

    cfg: CCConfig
    scn: Scenario
    times: np.ndarray          # [T] seconds (window-end times)
    delivered: np.ndarray      # [T, F] cumulative bytes
    rate: np.ndarray           # [T, F] RP rate (B/s)
    inst_thr: np.ndarray       # [T, F] window-mean delivery rate (B/s)
    max_q: np.ndarray          # [T] window-max hottest queue (bytes)
    n_paused: np.ndarray       # [T] window-max paused wires
    marked: np.ndarray         # [T, F] marking events in window
    cnp: np.ndarray            # [T, F] CNPs received in window
    n_nonmin: np.ndarray       # [T] window-max flows on non-minimal paths
    final: Any                 # FluidState (host)
    ctrl: np.ndarray = None    # [T, F] notification emissions in window
    trace_every: int = 1
    # PFC-pathology instrumentation (None on traces that predate it):
    pause_time: np.ndarray = None  # [T] pause wire-seconds in window
    vc_stall: np.ndarray = None    # [T, V] per-VC pause wire-seconds

    # -- wire format --------------------------------------------------------
    def to_dict(self, *, traces: bool = True, decimate: int = 1) -> dict:
        """JSON-ready dict (numpy-free scalars, tagged arrays).

        ``traces=False`` drops the trace arrays; ``decimate=k`` thins
        them by a further factor k.  The full form round-trips through
        ``json.dumps``/``loads`` + :meth:`from_dict` bit-exactly (see
        ``repro.core.serialize``)."""
        from .serialize import simresult_to_dict
        return simresult_to_dict(self, traces=traces, decimate=decimate)

    @classmethod
    def from_dict(cls, d: dict) -> "SimResult":
        from .serialize import simresult_from_dict
        return simresult_from_dict(d)

    # -- derived metrics ----------------------------------------------------
    def window_samples(self, seconds: float) -> int:
        """Trace samples spanning ``seconds`` (smoothing windows should
        be specified in time, not samples — sample spacing depends on
        ``trace_every``)."""
        dt_sample = self.trace_every * self.cfg.sim.dt
        return max(1, int(round(seconds / dt_sample)))

    def flow_throughput(self, window: int = 50) -> np.ndarray:
        """[T, F] delivery rate smoothed over `window` samples (B/s).

        Box filter over the sample axis via cumulative sums (equivalent
        to per-flow ``np.convolve(..., mode="same")`` but one vectorised
        pass over [T, F] instead of an O(F) python loop).
        """
        x = self.inst_thr.astype(np.float64)   # f32 cumsum would drift
        T = x.shape[0]
        w = max(1, min(window, T))
        c = np.concatenate([np.zeros((1,) + x.shape[1:]), np.cumsum(x, 0)])
        # same-mode box filter: sample t averages [t - w//2, t + (w-1)//2]
        lo = np.clip(np.arange(T) - w // 2, 0, T)
        hi = np.clip(np.arange(T) + (w - 1) // 2 + 1, 0, T)
        return (c[hi] - c[lo]) / w

    def aggregate_throughput(self, window: int = 50) -> np.ndarray:
        return self.flow_throughput(window).sum(axis=1)

    def completion_times(self, frac: float = 0.999) -> np.ndarray:
        """[F] time when `frac` of the flow's work was delivered.

        Volume-mode flows are measured against their declared volume
        (NaN if the run ended early); window-mode flows against the
        admitted bytes.  ``delivered`` is monotone per flow, so the
        first crossing is a vectorised argmax over the sample axis."""
        offered = np.asarray(self.final.offered)
        vol = np.asarray(self.scn.volume, dtype=np.float64)
        total = np.where(np.isfinite(vol), vol, offered)
        done = self.delivered >= frac * np.maximum(total, 1e-300)[None, :]
        first = done.argmax(axis=0)                   # 0 if never done too
        hit = done.any(axis=0) & (total > 0)
        return np.where(hit, self.times[first], np.nan)

    def completion_time(self, frac: float = 0.999) -> float:
        ct = self.completion_times(frac)
        return float(np.nanmax(ct)) if np.isfinite(ct).any() else float("nan")

    def mean_throughput_while_active(self) -> np.ndarray:
        """[F] mean delivery rate while the flow is live.

        Window mode: averaged over [t_start, t_stop).  Volume mode
        (t_stop = inf): volume / (completion - t_start).
        """
        t0 = np.asarray(self.scn.t_start, np.float64)
        t1 = np.asarray(self.scn.t_stop, np.float64)
        ct = self.completion_times()
        windowed = np.isfinite(t1)
        live = ((self.times[:, None] >= t0[None, :])
                & (self.times[:, None] < t1[None, :]))          # [T, F]
        n_live = live.sum(axis=0)
        mean_w = np.where(n_live > 0,
                          (self.inst_thr * live).sum(axis=0)
                          / np.maximum(n_live, 1), 0.0)
        span = ct - t0
        mean_v = np.where(np.isfinite(ct) & (span > 0),
                          self.delivered[-1] / np.maximum(span, 1e-300), 0.0)
        return np.where(windowed, mean_w, mean_v)

    def _real_flows(self) -> np.ndarray:
        """[F] bool — flows with actual offered work (padding rows in
        stacked sweeps carry zero rate and are excluded from
        fairness/tail statistics)."""
        return np.asarray(self.scn.gen_rate) > 0

    def jain_index(self) -> float:
        """Jain fairness over per-flow goodput while active, in [0, 1].

        1 = all real flows saw the same rate; 1/n = one flow took
        everything.  A first-class tuner objective (repro.tune).
        """
        thr = self.mean_throughput_while_active()[self._real_flows()]
        n = thr.size
        if n == 0:
            return float("nan")
        denom = n * float((thr ** 2).sum())
        return float(thr.sum()) ** 2 / denom if denom > 0 else 1.0

    def flow_slowdowns(self) -> np.ndarray:
        """[F_real] demand-normalised slowdown per real flow (>= ~1).

        Ideal rate = min(offered rate, line rate); slowdown = ideal /
        achieved mean rate while active — the fluid-model analogue of
        FCT slowdown (a flow throttled to half its unconstrained rate
        scores 2).
        """
        real = self._real_flows()
        thr = self.mean_throughput_while_active()[real]
        ideal = np.minimum(np.asarray(self.scn.gen_rate),
                           self.cfg.link.line_rate)[real]
        return ideal / np.maximum(thr, 1e-6 * self.cfg.link.line_rate)

    def p99_slowdown(self) -> float:
        """p99 of ``flow_slowdowns`` — the tail-latency tuner objective."""
        s = self.flow_slowdowns()
        return float(np.percentile(s, 99)) if s.size else float("nan")

    def victim_slowdown(self) -> float:
        """Mean slowdown over the scenario's designated victim flows.

        Victims (``Scenario.victim``) are flows that do not contribute
        to the congestion under test but share fabric with it — the
        HoL/pause-storm collateral the PFC-pathology scenarios measure.
        NaN when the scenario designates none (or none are real flows).
        """
        if self.scn.victim is None:
            return float("nan")
        vic = np.asarray(self.scn.victim, bool)[self._real_flows()]
        if not vic.any():
            return float("nan")
        return float(self.flow_slowdowns()[vic].mean())

    def pause_duration(self) -> float:
        """Total PFC pause wire-seconds over the run (sum over queues
        of pause level x dt).  NaN on traces predating the counter."""
        if self.pause_time is None:
            return float("nan")
        return float(np.asarray(self.pause_time).sum())

    def vc_stall_time(self) -> np.ndarray:
        """[V] pause wire-seconds per virtual channel ([1] when the
        config runs a single VC).  None on traces predating it."""
        if self.vc_stall is None:
            return None
        return np.asarray(self.vc_stall).sum(axis=0)

    def ctrl_per_mb(self) -> float:
        """Notification messages per delivered MB (control overhead).

        NaN when the trace predates the ``ctrl`` counter (old blobs).
        """
        if self.ctrl is None:
            return float("nan")
        mb = float(np.asarray(self.final.delivered).sum()) / 1e6
        return float(self.ctrl.sum()) / max(mb, 1e-9)

    def summary(self) -> dict:
        """Headline numbers for this run (one row of the Fig. 2/3
        table; ``SweepResult.summary`` is this, per point)."""
        thr = self.mean_throughput_while_active()
        return {
            "aggregate_gbps": float(thr.sum() / 1e9),
            "min_flow_gbps": float(thr.min() / 1e9),
            "completion_ms": float(self.completion_time() * 1e3),
            "peak_queue_kb": float(self.max_q.max() / 1e3),
            "delivered_mb": float(
                np.asarray(self.final.delivered).sum() / 1e6),
            "marks": int(self.marked.sum()),
            "cnps": int(self.cnp.sum()),
            "peak_nonmin_flows": int(self.n_nonmin.max()),
            "jain_index": self.jain_index(),
            "p99_slowdown": self.p99_slowdown(),
            "ctrl_per_mb": self.ctrl_per_mb(),
            "victim_slowdown": self.victim_slowdown(),
            "pause_s": self.pause_duration(),
            "vc_stall_s": None if self.vc_stall is None else
                [float(x) for x in self.vc_stall_time()],
        }


def run(scn: Scenario, cfg: CCConfig, n_steps: int | None = None,
        trace_every: int | None = None, *, reduce: str = "fused",
        use_kernels: "bool | str" = False,
        interpret: bool = False) -> SimResult:
    """Simulate one point and pull (decimated) traces to host.

    ``trace_every`` defaults to ``cfg.sim.trace_every``; pass 1 for a
    full-resolution trace.  ``n_steps`` is rounded up to a whole number
    of trace windows.  ``reduce`` / ``use_kernels`` / ``interpret``
    select the reduction engine and Pallas tier (see
    ``repro.core.fluid.fluid_step``); ``use_kernels="mega"`` runs each
    trace window as one whole-step megakernel launch with the fluid
    state VMEM-resident across all ``trace_every`` substeps.
    """
    n_samples, k = _resolve_steps(cfg, n_steps, trace_every)
    st0 = init_state(scn, cfg)
    n_vcs = int(getattr(cfg.link, "n_vcs", 1))
    from .fluid import kernel_tier
    if kernel_tier(use_kernels) == "mega":
        block = make_block_fn(scn, cfg, k, reduce=reduce,
                              interpret=interpret)
        final, tr = _run_block_scan(st0, block, n_samples)
    else:
        step = make_step_fn(scn, cfg, reduce=reduce,
                            use_kernels=use_kernels, interpret=interpret)
        final, tr = _run_scan(st0, step, n_samples, k,
                              float(cfg.sim.dt), n_vcs)
    # (i+1)*k first (exact int), then *dt — so decimated times are the
    # same floats as the strided full-resolution times
    times = (np.arange(n_samples) + 1) * k * cfg.sim.dt
    return SimResult(
        cfg=cfg, scn=scn, times=times,
        delivered=np.asarray(tr.delivered),
        rate=np.asarray(tr.rate),
        inst_thr=np.asarray(tr.inst_thr),
        max_q=np.asarray(tr.max_q),
        n_paused=np.asarray(tr.n_paused),
        marked=np.asarray(tr.marked),
        cnp=np.asarray(tr.cnp),
        n_nonmin=np.asarray(tr.n_nonmin),
        final=jax.device_get(final),
        ctrl=np.asarray(tr.ctrl),
        trace_every=k,
        pause_time=np.asarray(tr.pause_time),
        vc_stall=np.asarray(tr.vc_stall),
    )


def run_all_schemes(scn: Scenario, cfg: CCConfig,
                    n_steps: int | None = None) -> dict[str, SimResult]:
    """Scheme ablation as ONE batched device launch (see experiments).

    Kept for API compatibility; now a thin wrapper over a 3-point Sweep
    instead of three serial jit compilations.
    """
    from .experiments import Sweep
    schemes = (CCScheme.PFC_ONLY, CCScheme.DCQCN, CCScheme.DCQCN_REV)
    sweep = Sweep([(s.name, cfg.replace(scheme=s), scn) for s in schemes])
    res = sweep.run(n_steps=n_steps)
    return {s.name: res[s.name] for s in schemes}
