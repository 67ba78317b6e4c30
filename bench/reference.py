"""Plain NumPy reference of the fluid congestion-control model.

The yardstick that decides `correct`.  It follows the model's published
description (the source paper's section II and the DCQCN fluid model of
Zhu et al., SIGCOMM 2015) and shares no code, table or constant with the
program under test:

* fabrics are routed from their definition by ``path`` of
  ``bench/fabrics/<kind>.py`` (XGFT trees with D-mod-K up-routing today);
* one queue per directed link, at its sink end; every step is a Jacobi
  update from the pre-step state: generation, transfers (proportional
  service, PFC gate, strict-FIFO head-of-line factor), PFC hysteresis and
  the per-switch shared pool, marking (CP occupancy or ECP fair-grant),
  notification (NP/ENP window, delivered one feedback delay later) and
  reaction (fixed-rate PFC source, DCQCN RP, or ERP);
* traces are decimated every ``trace_every`` steps: cumulative fields at
  the window end, event counts and sums over the window, maxima of the
  hottest queue and of the paused-link count.

Every run of a batch is simulated as a disjoint copy of its fabric inside
one union network, so a batch of runs is one vectorised loop.  Each
arithmetic result is rounded to ``dtype`` (``float32`` is the precision
the configurations state; ``bfloat16`` makes the control), and per-link
sums accumulate in ``dtype`` in flow order.
"""

from __future__ import annotations

import dataclasses
import json

import ml_dtypes
import numpy as np

from bench.lookup import module

DTYPES = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16,
          "float64": np.float64}

MARKINGS = ("cp", "ecp")
NOTIFICATIONS = ("np", "enp")
REACTIONS = ("pfc", "rp", "erp")


@dataclasses.dataclass
class Run:
    """One simulated point: a fabric, its flows and one CC scheme."""

    fabric: dict              # the configuration's fabric (its kind names
                              # bench/fabrics/<kind>.py, which routes it)
    roll: int                 # digit rotation of the D-mod-K up choice
    src: np.ndarray           # [F] host ids
    dst: np.ndarray
    t_start: np.ndarray       # [F] s
    t_stop: np.ndarray        # [F] s
    volume: np.ndarray        # [F] B (inf = window-limited)
    rate: np.ndarray          # [F] B/s offered
    nic_buffer: np.ndarray    # [F] B
    scheme: tuple             # (marking, notification, reaction)
    link: dict                # line_rate, propagation_delay, mtu, buffers, pfc fracs
    dcqcn: dict
    rev: dict
    dt: float


# ---------------------------------------------------------------------------
# the union network of a batch of runs
# ---------------------------------------------------------------------------


class Network:
    """Flows of every run on disjoint copies of their fabrics."""

    def __init__(self, runs):
        link_id, switch_id = {}, {}
        sink_sw, link_run, flow_links = [], [], []
        path_cache = {}
        for r, run in enumerate(runs):
            fab = module("fabrics", run.fabric["kind"])
            fab_key = json.dumps(run.fabric, sort_keys=True)
            for s, d in zip(run.src.tolist(), run.dst.tolist()):
                key = (fab_key, run.roll, s, d)
                if key not in path_cache:
                    path_cache[key] = fab.path(run.fabric, run.roll, s, d)
                nodes = path_cache[key]
                ids = []
                for a, b in zip(nodes[:-1], nodes[1:]):
                    lk = (r, a, b)
                    if lk not in link_id:
                        link_id[lk] = len(link_id)
                        link_run.append(r)
                        if b[0] == "host":
                            sink_sw.append(-1)
                        else:
                            sink_sw.append(switch_id.setdefault(
                                (r, b), len(switch_id)))
                    ids.append(link_id[lk])
                flow_links.append(ids)
        self.n_links = len(link_id)
        self.n_switches = max(len(switch_id), 1)
        self.sink_switch = np.asarray(sink_sw, np.int64)
        self.link_run = np.asarray(link_run, np.int64)
        self.H = max(len(p) for p in flow_links)
        F = len(flow_links)
        self.routes = np.full((F, self.H), self.n_links, np.int64)
        for f, p in enumerate(flow_links):
            self.routes[f, :len(p)] = p
        self.hops = np.asarray([len(p) for p in flow_links], np.int64)
        self.flow_run = np.concatenate(
            [np.full(len(run.src), r) for r, run in enumerate(runs)])
        self.n_runs = len(runs)


def weyl_jitter(n: int) -> np.ndarray:
    """Per-flow recovery jitter in [-1, 1): Knuth's multiplicative hash
    of the flow index, scaled."""
    x = (np.arange(n, dtype=np.uint64) * np.uint64(2654435761)) % np.uint64(2 ** 32)
    return x.astype(np.float64) / 2 ** 31 - 1.0


def feedback_steps(hops: np.ndarray, link: dict, dt: float) -> np.ndarray:
    """CNP delay in steps: two trips of (propagation + one MTU
    serialisation) per hop plus 1 us NIC turnaround, at least 2."""
    per_hop = link["propagation_delay"] + link["mtu"] / link["line_rate"]
    rtt = 2 * hops * per_hop + 1e-6
    return np.maximum(2, np.round(rtt / dt)).astype(np.int64)


# ---------------------------------------------------------------------------
# simulation
# ---------------------------------------------------------------------------


def simulate(runs, n_steps: int, trace_every: int, dtype: str = "float32",
             sum_order: str = "flow"):
    """Run every point for ``n_steps`` (a multiple of ``trace_every``).

    ``dtype`` is the precision every result is rounded to; ``sum_order``
    the order per-link sums accumulate in (``flow`` or ``reverse``): the
    float64 run and the reversed float32 run are witnesses of how far two
    sound implementations of the model drift apart by rounding alone.

    Returns one dict per run: decimated traces (``delivered``, ``rate``,
    ``inst_thr``, ``marked``, ``cnp``, ``ctrl`` as [T, F]; ``max_q``,
    ``n_paused``, ``pause_time`` as [T]) and ``final`` per-flow state.
    """
    if n_steps % trace_every:
        raise ValueError("n_steps must be a whole number of trace windows")
    dtv = DTYPES[dtype]
    fs = np.float64 if dtype == "float64" else np.float32   # storage

    def R(x):
        """Round to the working precision."""
        x = np.asarray(x, fs)
        return x if dtv is fs else x.astype(dtv).astype(fs)

    net = Network(runs)
    F, H, L = len(net.flow_run), net.H, net.n_links
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
    pool_xoff = R(lk["shared_buffer"] * lk["pfc_xoff_frac"])
    port_buffer = R(lk["port_buffer"])
    gen_rate = per_flow(lambda r: r.rate)
    t_start = per_flow(lambda r: r.t_start)
    t_stop = per_flow(lambda r: r.t_stop)
    volume = per_flow(lambda r: r.volume)
    nic_buffer = per_flow(lambda r: r.nic_buffer)
    jitter = R(np.concatenate([weyl_jitter(len(r.src)) for r in runs]))
    rtt = np.concatenate([feedback_steps(net.hops[fr == i], r.link, r.dt)
                          for i, r in enumerate(runs)])
    mark_code = np.asarray([MARKINGS.index(runs[i].scheme[0]) for i in fr])
    notif_code = np.asarray([NOTIFICATIONS.index(runs[i].scheme[1]) for i in fr])
    react_code = np.asarray([REACTIONS.index(runs[i].scheme[2]) for i in fr])
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
    valid = hop < net.hops[:, None]
    widx = net.routes
    is_last = valid & (hop == net.hops[:, None] - 1)
    holds = valid & (hop < net.hops[:, None] - 1)
    sink = net.sink_switch
    caps_w = cap_ext[widx]
    flat = widx.ravel()

    order = {"flow": slice(None), "reverse": slice(None, None, -1)}[sum_order]

    def seg(vals):
        """Per-link sums [L + 1] of an [F, H] quantity, accumulated in
        flow order (or its reverse)."""
        acc = np.zeros(L + 1, dtv)
        np.add.at(acc, flat[order],
                  np.asarray(vals, fs).ravel().astype(dtv)[order])
        return acc.astype(fs)

    def per_run_max(x_link):
        out = np.zeros(net.n_runs, fs)
        np.maximum.at(out, net.link_run, x_link)
        return out

    def per_run_sum(x_link):
        out = np.zeros(net.n_runs, dtv)
        np.add.at(out, net.link_run, x_link.astype(dtv))
        return out.astype(fs)

    z = np.zeros(F, fs)
    st = dict(qh=np.zeros((F, H), fs), nicq=z, delivered=z, offered=z,
              dropped=z, est=np.zeros((F, H), fs), paused=np.zeros(L, fs),
              rate=R(np.minimum(gen_rate, line)), rp_target=R(np.minimum(gen_rate, line)),
              alpha=per_flow(lambda r: r.dcqcn["alpha_init"]), byte_cnt=z, tmr=z,
              alpha_tmr=z, bc_stage=np.zeros(F, np.int64),
              t_stage=np.zeros(F, np.int64), hold=z, np_tmr=R(np.ones(F)))
    emit_hist = np.zeros((n_steps, F), bool)
    tgt_hist = np.zeros((n_steps, F), fs)
    T_s = n_steps // trace_every
    out = dict(delivered=np.zeros((T_s, F), fs), rate=np.zeros((T_s, F), fs),
               inst_thr=np.zeros((T_s, F), fs), marked=np.zeros((T_s, F), np.int64),
               cnp=np.zeros((T_s, F), np.int64), ctrl=np.zeros((T_s, F), fs),
               max_q=np.zeros((T_s, net.n_runs), fs),
               n_paused=np.zeros((T_s, net.n_runs), np.int64),
               pause_time=np.zeros((T_s, net.n_runs), fs))
    win_dt = R(trace_every * runs[0].dt)
    fidx = np.arange(F)

    with np.errstate(invalid="ignore", divide="ignore", over="ignore"):
        for t in range(n_steps):
            s = st
            if t % trace_every == 0:
                d0 = s["delivered"]
                acc_mq = np.zeros(net.n_runs, fs)
                acc_np = np.zeros(net.n_runs, np.int64)
                acc_mk = np.zeros(F, np.int64)
                acc_cn = np.zeros(F, np.int64)
                acc_ct = np.zeros(F, fs)
                acc_pt = np.zeros(net.n_runs, fs)
            t_sec = R(fs(t) * dt)
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
            wire_open = R(fs(1) - pause_q[widx])
            next_open = np.concatenate([wire_open[:, 1:], np.ones((F, 1), fs)], axis=1)
            q_here = np.where(holds, s["qh"], fs(0))
            weight = R(src_q * wire_open)
            num, den, sum_w = seg(R(q_here * next_open)), seg(q_here), seg(weight)
            fifo_ok = np.where(den > 0, R(num / np.maximum(den, fs(1e-9))), fs(1))
            budget = R(R(caps_w * dt) * fifo_ok[widx])
            sw = sum_w[widx]
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
            # 3. PFC
            B = seg(np.where(holds, qh, fs(0)))[:L]
            n_act = seg(act.astype(fs))
            sum_dem = seg(np.where(act, dem, fs(0)))
            paused = np.where(B > xoff, fs(1),
                              np.where(B < xon, fs(0), s["paused"])).astype(fs)
            pool = np.zeros(net.n_switches, dtv)
            np.add.at(pool, np.maximum(sink, 0),
                      np.where(sink >= 0, B, fs(0)).astype(dtv))
            pool_hot = (pool.astype(fs) > pool_xoff).astype(fs)
            paused = np.maximum(paused, np.where(sink >= 0, pool_hot[np.maximum(sink, 0)],
                                                 fs(0)))
            # 4. marking
            B1_w = np.concatenate([B, [fs(0)]])[widx]
            present = (qh > 0) | (T > 0)
            share0 = R(caps_w / np.maximum(n_act[widx], fs(1)))
            under = dem < share0
            surplus = seg(np.where(act & under, R(share0 - dem), fs(0)))
            n_heavy = seg((act & ~under).astype(fs))
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
            back = t - rtt
            seen = back >= 0
            cnp = np.zeros(F, bool)
            cnp[seen] = emit_hist[back[seen], fidx[seen]]
            tgt_rx = np.zeros(F, fs)
            tgt_rx[seen] = tgt_hist[back[seen], fidx[seen]]
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
                hold=np.where(is_erp, hold, hold0), np_tmr=np_tmr)
            # trace window accumulators
            acc_mq = np.maximum(acc_mq, per_run_max(B))
            n_p = np.zeros(net.n_runs, np.int64)
            np.add.at(n_p, net.link_run, paused > 0.5)
            acc_np = np.maximum(acc_np, n_p)
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
                out["pause_time"][i] = acc_pt

    results = []
    for r in range(net.n_runs):
        sel = fr == r
        tr = {k: (v[:, sel] if v.shape[1] == F else v[:, r]) for k, v in out.items()}
        final = {k: (v[sel] if k != "paused" else v[net.link_run == r])
                 for k, v in st.items()}
        final["qh"] = final["qh"][:, :int(net.hops[sel].max())]
        final["est"] = final["est"][:, :int(net.hops[sel].max())]
        results.append(dict(trace=tr, final=final))
    return results
