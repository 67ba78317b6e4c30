"""Pure-jnp oracles (the correctness ground truth).

Each function is the straight-line mathematical definition with no tiling:
tests compare the attention kernels (``interpret=True``) against the
attention oracles over shape/dtype sweeps, and the fluid step's reaction
stages (``repro.core.cc``) against the reaction-point oracles.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# flash attention (train/prefill): causal / sliding-window / softcap
# ---------------------------------------------------------------------------

def attention_ref(q, k, v, *, causal: bool = True, window: int | None = None,
                  softcap: float = 0.0, scale: float | None = None):
    """q: [b, t, h, d]; k, v: [b, s, kv, d] (GQA) -> [b, t, h, d]."""
    b, t, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, t, kv, h // kv, d)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32)
    logits *= scale
    if softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    qpos = jnp.arange(t)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((t, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", w, v)
    return out.reshape(b, t, h, d)


# ---------------------------------------------------------------------------
# decode attention: single query vs (possibly masked) KV cache
# ---------------------------------------------------------------------------

def decode_attention_ref(q, k, v, valid, *, softcap: float = 0.0,
                         scale: float | None = None):
    """q: [b, h, d]; k, v: [b, s, kv, d]; valid: [b, s] bool -> [b, h, d]."""
    b, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, kv, h // kv, d)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k).astype(jnp.float32)
    logits *= scale
    if softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v)
    return out.reshape(b, h, d)


# ---------------------------------------------------------------------------
# reaction points: DCQCN RP, the paper's ERP, delay-target swift
# ---------------------------------------------------------------------------

class RPState(NamedTuple):
    rate: jax.Array        # [F] f32 B/s
    target: jax.Array      # [F]
    alpha: jax.Array       # [F]
    byte_cnt: jax.Array    # [F]
    tmr: jax.Array         # [F]
    alpha_tmr: jax.Array   # [F]
    bc_stage: jax.Array    # [F] f32 (integral-valued)
    t_stage: jax.Array     # [F]


class RPParams(NamedTuple):
    g: float
    rate_decrease: float
    timer_T: float
    byte_B: float
    rai: float
    rhai: float
    fr_stages: float
    min_rate: float
    line_rate: float
    dt: float


def rp_update_ref(st: RPState, cnp: jax.Array, p: RPParams) -> RPState:
    """One dt of the DCQCN RP state machine, vectorised over flows.

    Mirrors the ``rp`` reaction stage of repro.core.cc (same semantics,
    f32 stages instead of int32 so the whole state is one dtype).
    """
    g = p.g
    alpha_tmr = st.alpha_tmr + p.dt
    a_tick = alpha_tmr >= p.timer_T
    alpha = jnp.where(a_tick, (1 - g) * st.alpha, st.alpha)
    alpha_tmr = jnp.where(a_tick, 0.0, alpha_tmr)

    target = jnp.where(cnp, st.rate, st.target)
    rate = jnp.where(cnp, st.rate * (1 - alpha * p.rate_decrease), st.rate)
    alpha = jnp.where(cnp, (1 - g) * alpha + g, alpha)
    byte_cnt = jnp.where(cnp, 0.0, st.byte_cnt + st.rate * p.dt)
    tmr = jnp.where(cnp, 0.0, st.tmr + p.dt)
    alpha_tmr = jnp.where(cnp, 0.0, alpha_tmr)
    bc_stage = jnp.where(cnp, 0.0, st.bc_stage)
    t_stage = jnp.where(cnp, 0.0, st.t_stage)

    b_ev = byte_cnt >= p.byte_B
    t_ev = tmr >= p.timer_T
    byte_cnt = jnp.where(b_ev, 0.0, byte_cnt)
    tmr = jnp.where(t_ev, 0.0, tmr)
    bc_stage = bc_stage + b_ev
    t_stage = t_stage + t_ev
    ev = b_ev | t_ev
    imax = jnp.maximum(bc_stage, t_stage)
    imin = jnp.minimum(bc_stage, t_stage)
    in_fr = imax <= p.fr_stages
    in_hyper = imin > p.fr_stages
    target = jnp.where(ev & ~in_fr & ~in_hyper, target + p.rai, target)
    target = jnp.where(ev & in_hyper,
                       target + p.rhai * (imin - p.fr_stages), target)
    rate = jnp.where(ev, 0.5 * (rate + target), rate)
    rate = jnp.clip(rate, p.min_rate, p.line_rate)
    target = jnp.clip(target, p.min_rate, p.line_rate)
    return RPState(rate, target, alpha, byte_cnt, tmr, alpha_tmr,
                   bc_stage, t_stage)


class ERPParams(NamedTuple):
    settle: float
    hold: float
    min_rate: float
    line_rate: float
    dt: float


def erp_update_ref(rate, hold, cnp, tgt_rx, slope, p: ERPParams):
    """One dt of the paper's ERP: jump to signalled fair share, hold,
    desynchronised additive recovery.  All [F] f32."""
    rate = jnp.where(cnp, jnp.maximum(p.settle * tgt_rx, p.min_rate), rate)
    hold = jnp.where(cnp, p.hold, jnp.maximum(hold - p.dt, 0.0))
    rate = jnp.where(~cnp & (hold <= 0), rate + slope * p.dt, rate)
    rate = jnp.clip(rate, p.min_rate, p.line_rate)
    return rate, hold


def swift_update_ref(rate, cool, qdelay, *, target, beta, ai, guard,
                     min_rate, line_rate, dt):
    """One dt of the delay-target reaction (Swift-like), [F] f32.

    Multiplicative decrease proportional to the excess of the path
    queuing-delay estimate over ``target`` — bounded by ``beta`` and
    paced by the ``guard`` cool-down — additive recovery below target.
    The ``swift`` reaction stage of repro.core.cc calls this for its
    hard path (exact f32 parity is a tier-1 test).
    """
    cool = jnp.maximum(cool - dt, 0.0)
    over = qdelay > target
    can = cool <= 0.0
    factor = 1.0 - beta * (qdelay - target) / jnp.maximum(qdelay, 1e-12)
    dec = jnp.maximum(rate * jnp.maximum(factor, 1.0 - beta), min_rate)
    rate = jnp.where(over & can, dec,
                     jnp.where(over, rate, rate + ai * dt))
    cool = jnp.where(over & can, guard, cool)
    rate = jnp.clip(rate, min_rate, line_rate)
    return rate, cool
