"""Plain NumPy reference of the fluid model with adaptive routing and
virtual channels: the yardstick of the multi-path cells.

It extends ``bench/reference.py`` (whose helpers it uses and whose
semantics it keeps: at one candidate path and one queue per wire it is
bitwise that reference wherever no result is subnormal) with what those
cells add, and like it imports nothing of the program:

* K candidate paths per flow, routed from the fabric's definition:
  slot 0 by ``path`` of ``bench/fabrics/<kind>.py``, slots 1..K-1 by its
  ``detour`` (the Valiant route, one draw per (seed, s, d, slot));
* selection at the top of every step: ``min`` keeps slot 0; ``valiant``
  takes the sampled detour at the flow's start; ``ugal`` (UGAL-L) takes
  it, at the flow's start and whenever a CNP arrives, only if its hop
  count times the summed pre-step backlog of its wires is strictly below
  the minimal path's.  The sampled detour of flow f at step t is slot
  ``1 + (f + t) mod n_alt`` (f counted within its run, n_alt its detour
  slots).  A switching flow keeps its queued bytes by hop position;
  its CNP delay stays the minimal path's;
* V queues per wire at its sink end: slot 0 rides queue 0, detours
  queue 1 (``routing.vc_mode`` "slot").  Each queue has its own FIFO
  factor, PFC state and thresholds (the port's over V); the wire's
  capacity, fair grants, oversubscription, the shared pool and the UGAL
  backlog take the sum over its queues.

Traces add ``n_nonmin`` (window maximum of the flows on a detour) and
the final state ``path_idx`` (each flow's candidate).

Results below float32's smallest normal number are flushed to zero, as
the accelerator's float32 does.  The model needs it: a queue's FIFO
factor divides by ``max(backlog, 1e-9)``, so a residue of a few
subnormal bytes left by a path switch nearly stalls the wire where the
flushed residue leaves it open.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np

from bench.lookup import module
from bench.reference import (DTYPES, MARKINGS, NOTIFICATIONS, REACTIONS,
                             feedback_steps, weyl_jitter)
from bench.reference import Run as PlainRun

MODES = ("min", "valiant", "ugal")


@dataclasses.dataclass
class Run(PlainRun):
    """One point: ``bench.reference.Run`` plus its routing."""

    routing: str = "min"       # min | valiant | ugal
    n_paths: int = 1           # K candidates a flow
    route_seed: int = 0        # the detours' draw


class Network:
    """Flows of every run on disjoint copies of their fabrics; every
    candidate path's links, numbered in the order flows first cross them
    (slot 0 before the detours)."""

    def __init__(self, runs):
        link_id, switch_id = {}, {}
        sink_sw, link_run, flow_paths = [], [], []
        path_cache = {}
        for r, run in enumerate(runs):
            fab = module("fabrics", run.fabric["kind"])
            fab_key = json.dumps(run.fabric, sort_keys=True)
            for s, d in zip(run.src.tolist(), run.dst.tolist()):
                cands = []
                for k in range(run.n_paths):
                    key = (fab_key, run.roll, s, d, k, run.route_seed if k else 0)
                    if key not in path_cache:
                        path_cache[key] = fab.path(run.fabric, run.roll, s, d) if k == 0 \
                            else fab.detour(run.fabric, s, d, run.route_seed, k)
                    ids = []
                    nodes = path_cache[key]
                    for a, b in zip(nodes[:-1], nodes[1:]):
                        lk = (r, a, b)
                        if lk not in link_id:
                            link_id[lk] = len(link_id)
                            link_run.append(r)
                            sink_sw.append(-1 if b[0] == "host" else
                                           switch_id.setdefault((r, b), len(switch_id)))
                        ids.append(link_id[lk])
                    cands.append(ids)
                flow_paths.append(cands)
        self.n_links = len(link_id)
        self.n_switches = max(len(switch_id), 1)
        self.sink_switch = np.asarray(sink_sw, np.int64)
        self.link_run = np.asarray(link_run, np.int64)
        self.K = max(len(c) for c in flow_paths)
        self.H = max(len(p) for c in flow_paths for p in c)
        F = len(flow_paths)
        self.routes = np.full((F, self.K, self.H), self.n_links, np.int64)
        self.hops = np.zeros((F, self.K), np.int64)
        for f, cands in enumerate(flow_paths):
            for k, p in enumerate(cands):
                self.routes[f, k, :len(p)] = p
                self.hops[f, k] = len(p)
        self.flow_run = np.concatenate(
            [np.full(len(run.src), r) for r, run in enumerate(runs)])
        self.n_runs = len(runs)


def simulate(runs, n_steps: int, trace_every: int, dtype: str = "float32",
             sum_order: str = "flow"):
    """Run every point for ``n_steps`` (a multiple of ``trace_every``);
    arguments and results as ``bench.reference.simulate``, plus the
    ``n_nonmin`` trace [T] and the final ``path_idx`` [F].  In float32
    and bfloat16 a result below the format's smallest normal number is
    flushed to zero, as the accelerator's arithmetic does; float64 keeps
    it."""
    if n_steps % trace_every:
        raise ValueError("n_steps must be a whole number of trace windows")
    dtv = DTYPES[dtype]
    fs = np.float64 if dtype == "float64" else np.float32   # storage

    flush = dtype != "float64"
    tiny = fs(np.finfo(np.float32).tiny)       # bfloat16 shares the exponent range

    def R(x):
        """Round to the working precision (flushing subnormals)."""
        x = np.asarray(x, fs)
        x = x if dtv is fs else x.astype(dtv).astype(fs)
        return np.where(np.abs(x) < tiny, fs(0), x) if flush else x

    net = Network(runs)
    F, K, H, L = len(net.flow_run), net.K, net.H, net.n_links
    V = int(runs[0].link.get("n_vcs", 1))
    if any(int(r.link.get("n_vcs", 1)) != V for r in runs):
        raise ValueError("every run of a batch has the same queues a wire")
    S = L * V
    fr = net.flow_run

    def per_flow(get):
        return R(np.concatenate([np.broadcast_to(np.asarray(get(r), np.float64),
                                                 (len(r.src),)) for r in runs]))

    dt = R(runs[0].dt)
    line = per_flow(lambda r: r.link["line_rate"])
    cap_ext = R(np.concatenate([np.full(L, runs[0].link["line_rate"]), [np.inf]]))
    lk = runs[0].link
    xoff = R(lk["port_buffer"] * lk["pfc_xoff_frac"])
    xon = R(lk["port_buffer"] * lk["pfc_xon_frac"])
    xoff_q, xon_q = (xoff, xon) if V == 1 else (R(xoff / fs(V)), R(xon / fs(V)))
    pool_xoff = R(lk["shared_buffer"] * lk["pfc_xoff_frac"])
    port_buffer = R(lk["port_buffer"])
    gen_rate = per_flow(lambda r: r.rate)
    t_start = per_flow(lambda r: r.t_start)
    t_stop = per_flow(lambda r: r.t_stop)
    volume = per_flow(lambda r: r.volume)
    nic_buffer = per_flow(lambda r: r.nic_buffer)
    jitter = R(np.concatenate([weyl_jitter(len(r.src)) for r in runs]))
    rtt = np.concatenate([feedback_steps(net.hops[fr == i, 0], r.link, r.dt)
                          for i, r in enumerate(runs)])
    mark_code = np.asarray([MARKINGS.index(runs[i].scheme[0]) for i in fr])
    notif_code = np.asarray([NOTIFICATIONS.index(runs[i].scheme[1]) for i in fr])
    react_code = np.asarray([REACTIONS.index(runs[i].scheme[2]) for i in fr])
    route_code = np.asarray([MODES.index(runs[i].routing) for i in fr])
    kmin = per_flow(lambda r: r.dcqcn["kmin"])
    ecp_thresh = per_flow(lambda r: r.rev["detect_threshold"])
    ecp_slack = per_flow(lambda r: r.rev["ecp_fairness_slack"])
    beta = per_flow(lambda r: r.rev["ecp_rate_ewma"])[:, None]
    drain_gain = per_flow(lambda r: r.rev["erp_drain_gain"])
    window = np.where(notif_code == 0, per_flow(lambda r: r.dcqcn["cnp_window"]),
                      per_flow(lambda r: r.rev["enp_coalesce"]))
    g = per_flow(lambda r: r.dcqcn["g"])
    rdf = per_flow(lambda r: r.dcqcn["rate_decrease_factor"])
    timer_T = per_flow(lambda r: r.dcqcn["timer_T"])
    byte_B = per_flow(lambda r: r.dcqcn["byte_counter_B"])
    rai = per_flow(lambda r: r.dcqcn["rai"])
    rhai = per_flow(lambda r: r.dcqcn["rhai"])
    fr_stages = np.concatenate([np.full(len(r.src), int(r.dcqcn["fr_stages"]))
                                for r in runs])
    rp_min = per_flow(lambda r: r.dcqcn["min_rate"])
    erp_settle = per_flow(lambda r: r.rev["erp_settle"])
    erp_rai = per_flow(lambda r: r.rev["erp_rai"])
    erp_jit = per_flow(lambda r: r.rev["erp_jitter"])
    erp_hold = per_flow(lambda r: r.rev["erp_hold"])
    erp_min = per_flow(lambda r: r.rev["min_rate"])

    hop = np.arange(H)[None, :]
    fidx = np.arange(F)
    # each flow's index within its run, and its detour slots
    f_in_run = np.concatenate([np.arange(len(r.src)) for r in runs])
    n_alt = (net.hops[:, 1:] > 0).sum(axis=1)
    slot_vc = np.minimum(np.arange(K), min(1, V - 1))          # "slot": detours on 1
    sink = net.sink_switch
    queue_run = np.repeat(net.link_run, V)

    order = {"flow": slice(None), "reverse": slice(None, None, -1)}[sum_order]

    def seg(vals, flat, n):
        """Sums [n + 1] of an [F, H] quantity over the slots ``flat``,
        accumulated in flow order (or its reverse)."""
        acc = np.zeros(n + 1, dtv)
        np.add.at(acc, flat[order], np.asarray(vals, fs).ravel().astype(dtv)[order])
        return R(acc)

    def to_wire(x_ext):
        """Per-queue sums [S + 1] folded to per-wire [L + 1]."""
        if V == 1:
            return x_ext
        w = x_ext[:S].reshape(L, V)
        acc = w[:, 0]
        for v in range(1, V):
            acc = R(acc + w[:, v])
        return np.concatenate([acc, x_ext[S:]])

    def layout(k_idx):
        """(wire [F, H], queue [F, H], hops [F]) of candidate ``k_idx``."""
        r, h = net.routes[fidx, k_idx], net.hops[fidx, k_idx]
        valid = hop < h[:, None]
        w = np.where(valid, r, L)
        return w, np.where(valid, w * V + slot_vc[k_idx][:, None], S), h

    def per_run_max(x_q):
        out = np.zeros(net.n_runs, fs)
        np.maximum.at(out, queue_run, x_q)
        return out

    def per_run_sum(x_q):
        out = np.zeros(net.n_runs, dtv)
        np.add.at(out, queue_run, x_q.astype(dtv))
        return out.astype(fs)

    z = np.zeros(F, fs)
    st = dict(qh=np.zeros((F, H), fs), nicq=z, delivered=z, offered=z,
              dropped=z, est=np.zeros((F, H), fs), paused=np.zeros(S, fs),
              rate=R(np.minimum(gen_rate, line)), rp_target=R(np.minimum(gen_rate, line)),
              alpha=per_flow(lambda r: r.dcqcn["alpha_init"]), byte_cnt=z, tmr=z,
              alpha_tmr=z, bc_stage=np.zeros(F, np.int64),
              t_stage=np.zeros(F, np.int64), hold=z, np_tmr=R(np.ones(F)),
              path_idx=np.zeros(F, np.int64))
    emit_hist = np.zeros((n_steps, F), bool)
    tgt_hist = np.zeros((n_steps, F), fs)
    T_s = n_steps // trace_every
    out = dict(delivered=np.zeros((T_s, F), fs), rate=np.zeros((T_s, F), fs),
               inst_thr=np.zeros((T_s, F), fs), marked=np.zeros((T_s, F), np.int64),
               cnp=np.zeros((T_s, F), np.int64), ctrl=np.zeros((T_s, F), fs),
               max_q=np.zeros((T_s, net.n_runs), fs),
               n_paused=np.zeros((T_s, net.n_runs), np.int64),
               pause_time=np.zeros((T_s, net.n_runs), fs),
               n_nonmin=np.zeros((T_s, net.n_runs), np.int64))
    win_dt = R(trace_every * runs[0].dt)

    with np.errstate(invalid="ignore", divide="ignore", over="ignore"):
        for t in range(n_steps):
            s = st
            if t % trace_every == 0:
                d0 = s["delivered"]
                acc_mq = np.zeros(net.n_runs, fs)
                acc_np = np.zeros(net.n_runs, np.int64)
                acc_nm = np.zeros(net.n_runs, np.int64)
                acc_mk = np.zeros(F, np.int64)
                acc_cn = np.zeros(F, np.int64)
                acc_ct = np.zeros(F, fs)
                acc_pt = np.zeros(net.n_runs, fs)
            t_sec = R(fs(t) * dt)
            # CNPs landing this step: emitted one feedback delay ago
            back = t - rtt
            seen = back >= 0
            cnp = np.zeros(F, bool)
            cnp[seen] = emit_hist[back[seen], fidx[seen]]
            tgt_rx = np.zeros(F, fs)
            tgt_rx[seen] = tgt_hist[back[seen], fidx[seen]]
            # 0. path selection
            path_idx = s["path_idx"]
            if K > 1:
                w_old, q_old, h_old = layout(path_idx)
                held = hop < h_old[:, None] - 1
                B_prev = to_wire(seg(np.where(held, s["qh"], fs(0)), q_old.ravel(), S))

                def cost(k_idx):
                    w, _, h = layout(k_idx)
                    q = np.zeros(F, fs)
                    for j in range(H):
                        q = R(q + np.where(w[:, j] < L, B_prev[w[:, j]], fs(0)))
                    return R(h.astype(fs) * q)

                samp = np.where(n_alt > 0, 1 + (f_in_run + t) % np.maximum(n_alt, 1), 0)
                ugal_pick = np.where(cost(samp) < cost(np.zeros(F, np.int64)), samp, 0)
                starting = (t_sec >= t_start) & (R(t_sec - dt) < t_start)
                epoch = starting | ((route_code == 2) & cnp)
                pick = np.where(route_code == 1, samp, ugal_pick)
                path_idx = np.where(route_code == 0, 0, np.where(epoch, pick, path_idx))
            widx, qidx, hops = layout(path_idx)
            valid = widx < L
            is_last = valid & (hop == hops[:, None] - 1)
            holds = valid & (hop < hops[:, None] - 1)
            caps_w = cap_ext[widx]
            flat = qidx.ravel()
            # 1. generation
            active = (t_sec >= t_start) & (t_sec < t_stop)
            gen = R(np.where(active, gen_rate, fs(0)) * dt)
            gen = np.minimum(gen, np.maximum(R(volume - s["offered"]), fs(0)))
            nicq = R(s["nicq"] + gen)
            over = np.maximum(R(nicq - nic_buffer), fs(0))
            nicq = R(nicq - over)
            offered = R(R(s["offered"] + gen) - over)
            dropped = R(s["dropped"] + over)
            np_tmr_t = R(s["np_tmr"] + dt)
            # 2. transfers
            src_inj = np.minimum(nicq, R(np.minimum(s["rate"], line) * dt))
            src_q = np.concatenate([src_inj[:, None], s["qh"][:, :-1]], axis=1)
            src_q = np.where(valid, src_q, fs(0))
            pause_q = np.concatenate([s["paused"], [fs(0)]])
            wire_open = R(fs(1) - pause_q[qidx])
            next_open = np.concatenate([wire_open[:, 1:], np.ones((F, 1), fs)], axis=1)
            q_here = np.where(holds, s["qh"], fs(0))
            weight = R(src_q * wire_open)
            num, den, sum_w = (seg(R(q_here * next_open), flat, S), seg(q_here, flat, S),
                               seg(weight, flat, S))
            fifo_ok = np.where(den > 0, R(num / np.maximum(den, fs(1e-9))), fs(1))
            budget = R(R(caps_w * dt) * fifo_ok[qidx])
            sw = to_wire(sum_w)[widx]
            share = np.where(sw > 0, R(R(budget * weight) / np.maximum(sw, fs(1e-9))),
                             fs(0))
            T = np.minimum(weight, share)
            nicq = R(nicq - T[:, 0])
            qh = R(s["qh"] - np.concatenate([T[:, 1:], np.zeros((F, 1), fs)], axis=1))
            qh = R(qh + np.where(holds, T, fs(0)))
            qh = np.maximum(qh, fs(0))
            deliv = np.where(is_last, T, fs(0))
            deliv_step = deliv[:, 0]
            for h in range(1, H):
                deliv_step = R(deliv_step + deliv[:, h])
            delivered = R(s["delivered"] + deliv_step)
            est = R(R(R(fs(1) - beta) * s["est"]) + R(beta * R(T / dt)))
            dem = np.where(valid, np.concatenate([est[:, :1], est[:, :-1]], axis=1),
                           fs(0))
            act = (dem > fs(1e6)) & valid
            # 3. PFC, per queue; the shared pool counts the wire's queues
            B = seg(np.where(holds, qh, fs(0)), flat, S)[:S]
            n_act = to_wire(seg(act.astype(fs), flat, S))
            sum_dem = to_wire(seg(np.where(act, dem, fs(0)), flat, S))
            paused = np.where(B > xoff_q, fs(1),
                              np.where(B < xon_q, fs(0), s["paused"])).astype(fs)
            B_wire = to_wire(np.concatenate([B, [fs(0)]]))[:L]
            pool = np.zeros(net.n_switches, dtv)
            np.add.at(pool, np.maximum(sink, 0),
                      np.where(sink >= 0, B_wire, fs(0)).astype(dtv))
            pool_hot = (pool.astype(fs) > pool_xoff).astype(fs)
            paused = np.maximum(paused, np.repeat(
                np.where(sink >= 0, pool_hot[np.maximum(sink, 0)], fs(0)), V))
            # 4. marking
            B1_w = np.concatenate([B, [fs(0)]])[qidx]
            present = (qh > 0) | (T > 0)
            share0 = R(caps_w / np.maximum(n_act[widx], fs(1)))
            under = dem < share0
            surplus = to_wire(seg(np.where(act & under, R(share0 - dem), fs(0)), flat, S))
            n_heavy = to_wire(seg((act & ~under).astype(fs), flat, S))
            grant = np.where(under, dem, R(share0 + R(surplus[widx] /
                                                      np.maximum(n_heavy[widx], fs(1)))))
            grant = np.where(act, grant, caps_w)
            oversub = sum_dem[widx] > caps_w
            inf_col = np.full((F, 1), np.inf, fs)
            grant_next = np.where(holds, np.concatenate([grant[:, 1:], inf_col], axis=1),
                                  fs(np.inf))
            dem_next = np.concatenate([dem[:, 1:], np.zeros((F, 1), fs)], axis=1)
            over_next = np.concatenate([oversub[:, 1:], np.zeros((F, 1), bool)], axis=1)
            thresh = np.where(mark_code == 0, kmin, ecp_thresh)[:, None]
            base = (B1_w > thresh) & present & holds
            qexc = np.clip(R(R(B1_w - thresh) / port_buffer), fs(0), fs(1))
            finite = np.isfinite(grant_next)
            sev = np.where(finite, R(np.where(finite, grant_next, fs(0))
                                     * R(fs(1) - R(drain_gain[:, None] * qexc))),
                           fs(np.inf))
            congesting = over_next & (dem_next > R(ecp_slack[:, None] * grant_next))
            mark = np.where((mark_code == 0)[:, None], base, base & congesting)
            marked = mark.any(axis=1)
            tgt = np.min(np.where(mark, sev, fs(np.inf)), axis=1)
            tgt = np.where(np.isfinite(tgt), tgt, line)
            # 5. notification: one per window, landing one feedback delay later
            emit = marked & (np_tmr_t >= window)
            np_tmr = np.where(emit, fs(0), np_tmr_t)
            emit_hist[t], tgt_hist[t] = emit, tgt
            # 6. reaction
            rate0, hold0 = s["rate"], s["hold"]
            # DCQCN RP
            alpha_tmr = R(s["alpha_tmr"] + dt)
            a_tick = alpha_tmr >= timer_T
            alpha = np.where(a_tick, R(R(fs(1) - g) * s["alpha"]), s["alpha"])
            alpha_tmr = np.where(a_tick, fs(0), alpha_tmr)
            rp_target = np.where(cnp, rate0, s["rp_target"])
            rp_rate = np.where(cnp, R(rate0 * R(fs(1) - R(alpha * rdf))), rate0)
            alpha = np.where(cnp, R(R(R(fs(1) - g) * alpha) + g), alpha)
            byte_cnt = np.where(cnp, fs(0), R(s["byte_cnt"] + R(rate0 * dt)))
            tmr = np.where(cnp, fs(0), R(s["tmr"] + dt))
            alpha_tmr = np.where(cnp, fs(0), alpha_tmr)
            bc_stage = np.where(cnp, 0, s["bc_stage"])
            t_stage = np.where(cnp, 0, s["t_stage"])
            b_ev, t_ev = byte_cnt >= byte_B, tmr >= timer_T
            byte_cnt = np.where(b_ev, fs(0), byte_cnt)
            tmr = np.where(t_ev, fs(0), tmr)
            bc_stage = bc_stage + b_ev
            t_stage = t_stage + t_ev
            ev = b_ev | t_ev
            imax, imin = np.maximum(bc_stage, t_stage), np.minimum(bc_stage, t_stage)
            in_fr, in_hyper = imax <= fr_stages, imin > fr_stages
            rp_target = np.where(ev & ~in_fr & ~in_hyper, R(rp_target + rai), rp_target)
            rp_target = np.where(ev & in_hyper,
                                 R(rp_target + R(rhai * R(imin - fr_stages))), rp_target)
            rp_rate = np.where(ev, R(fs(0.5) * R(rp_rate + rp_target)), rp_rate)
            rp_rate = np.clip(rp_rate, rp_min, line)
            rp_target = np.clip(rp_target, rp_min, line)
            # ERP
            settle = np.maximum(R(erp_settle * tgt_rx), erp_min)
            erp_rate = np.where(cnp, settle, rate0)
            hold = np.where(cnp, erp_hold, np.maximum(R(hold0 - dt), fs(0)))
            slope = R(R(erp_rai * R(fs(1) + R(erp_jit * jitter))) * dt)
            erp_rate = np.where(~cnp & (hold <= 0), R(erp_rate + slope), erp_rate)
            erp_rate = np.clip(erp_rate, erp_min, line)
            # select each flow's reaction; unselected stages keep their state
            is_rp, is_erp = react_code == 1, react_code == 2
            rate = np.where(is_rp, rp_rate,
                            np.where(is_erp, erp_rate, np.minimum(gen_rate, line)))
            st = dict(
                qh=qh, nicq=nicq, delivered=delivered, offered=offered,
                dropped=dropped, est=est, paused=paused, rate=R(rate),
                rp_target=np.where(is_rp, rp_target, s["rp_target"]),
                alpha=np.where(is_rp, alpha, s["alpha"]),
                byte_cnt=np.where(is_rp, byte_cnt, s["byte_cnt"]),
                tmr=np.where(is_rp, tmr, s["tmr"]),
                alpha_tmr=np.where(is_rp, alpha_tmr, s["alpha_tmr"]),
                bc_stage=np.where(is_rp, bc_stage, s["bc_stage"]),
                t_stage=np.where(is_rp, t_stage, s["t_stage"]),
                hold=np.where(is_erp, hold, hold0), np_tmr=np_tmr,
                path_idx=path_idx)
            # trace window accumulators
            acc_mq = np.maximum(acc_mq, per_run_max(B))
            n_p = np.zeros(net.n_runs, np.int64)
            np.add.at(n_p, queue_run, paused > 0.5)
            acc_np = np.maximum(acc_np, n_p)
            acc_nm = np.maximum(acc_nm, np.bincount(fr, path_idx > 0, net.n_runs)
                                .astype(np.int64))
            acc_mk += marked
            acc_cn += cnp
            acc_ct = R(acc_ct + emit.astype(fs))
            acc_pt = R(acc_pt + R(per_run_sum(paused) * dt))
            if (t + 1) % trace_every == 0:
                i = t // trace_every
                out["delivered"][i], out["rate"][i] = delivered, st["rate"]
                out["inst_thr"][i] = R(R(delivered - d0) / win_dt)
                out["marked"][i], out["cnp"][i], out["ctrl"][i] = acc_mk, acc_cn, acc_ct
                out["max_q"][i], out["n_paused"][i] = acc_mq, acc_np
                out["pause_time"][i], out["n_nonmin"][i] = acc_pt, acc_nm

    results = []
    for r in range(net.n_runs):
        sel = fr == r
        tr = {k: (v[:, sel] if v.shape[1] == F else v[:, r]) for k, v in out.items()}
        final = {k: (v[sel] if k != "paused" else v[queue_run == r])
                 for k, v in st.items()}
        h_run = int(net.hops[sel].max())
        final["qh"] = final["qh"][:, :h_run]
        final["est"] = final["est"][:, :h_run]
        results.append(dict(trace=tr, final=final))
    return results


def path_gaps(prog: list, refs: list) -> dict:
    """``nonmin_gap``: widest gap in the traced count of flows on a
    detour, as a share of the run's flows; ``path_flips``: share of the
    run's flows whose final candidate differs.  Each the widest over
    the runs."""
    nonmin = flips = 0.0
    for p, r in zip(prog, refs):
        F = r["final"]["path_idx"].shape[0]
        gap = np.abs(np.asarray(p["trace"]["n_nonmin"], np.int64)
                     - r["trace"]["n_nonmin"]).max(initial=0)
        nonmin = max(nonmin, float(gap) / F)
        diff = np.asarray(p["final"]["path_idx"])[:F] != r["final"]["path_idx"]
        flips = max(flips, float(diff.mean()))
    return dict(nonmin_gap=nonmin, path_flips=flips)
