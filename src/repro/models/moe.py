"""Mixture-of-Experts block: top-k routing with capacity-based dispatch.

Grouped Mesh-TensorFlow-style dispatch: tokens route *within their own
sequence* (group = batch row), so dispatch/combine tensors are
``[b, t, experts, capacity]`` einsum operands that XLA fuses into dots.
Under the ``experts -> model`` sharding the expert compute lowers to the
canonical all-to-all + expert-parallel matmuls — exactly the incast-ish
fabric traffic the paper's CC mechanism targets (benchmarks/cosim.py
feeds these bytes into the CLOS fluid model).

Supports mixtral (8e top-2) and deepseek-moe (64e top-6 + 2 shared,
fine-grained d_ff, first layer dense).  The sort-based (dropless) dispatch
in §Perf replaces this one-hot path for the MoE hillclimb cell.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..dist.sharding import shard
from .config import ModelConfig
from .layers import ParamDef, apply_mlp, mlp_defs


def moe_defs(cfg: ModelConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    defs = {
        "router": ParamDef((d, m.n_experts), ("embed", None), "scaled"),
        "wi": ParamDef((m.n_experts, d, m.d_ff_expert),
                       ("experts", "fsdp", "mlp"), "scaled"),
        "wg": ParamDef((m.n_experts, d, m.d_ff_expert),
                       ("experts", "fsdp", "mlp"), "scaled"),
        "wo": ParamDef((m.n_experts, m.d_ff_expert, d),
                       ("experts", "mlp", "fsdp"), "scaled"),
    }
    if m.n_shared:
        defs["shared"] = mlp_defs(d, m.d_ff_shared, "swiglu")
    return defs


def capacity_of(cfg: ModelConfig, t: int) -> int:
    m = cfg.moe
    return max(1, int(m.capacity_factor * t * m.top_k / m.n_experts))


def apply_moe(p: dict, cfg: ModelConfig, x: jax.Array):
    """x: [b, t, d] -> (y, aux_loss)."""
    m = cfg.moe
    b, t, d = x.shape
    e, k = m.n_experts, m.top_k
    c = capacity_of(cfg, t)

    if cfg.moe_tokens == "gathered":
        # §Perf: all-gather the seq axis once at entry; the dispatch
        # einsums then contract an unsharded t (no [b,e,c,d] psums) and
        # the exit constraint reduce-scatters y back to seq shards.
        x = shard(x, "batch", None, "act_embed")

    gate_logits = jnp.einsum(
        "btd,de->bte", x, p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(gate_logits, axis=-1)            # [b,t,e]
    gate_w, gate_idx = jax.lax.top_k(probs, k)              # [b,t,k]
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    if cfg.moe_impl == "sort":
        y = _dispatch_sort(p, cfg, x, gate_w, gate_idx, c)
        if m.n_shared:
            y = y + apply_mlp(p["shared"], x, "swiglu")
        me = probs.mean((0, 1))
        ce = jax.nn.one_hot(gate_idx[..., 0], e).mean((0, 1))
        aux = m.router_aux_weight * e * jnp.sum(me * ce)
        return y, aux

    # slot position of each (token, k) inside its expert's capacity buffer
    oh = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)       # [b,t,k,e]
    flat = oh.reshape(b, t * k, e)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(b, t, k, e)
    pos = (pos * oh).sum(-1)                                # [b,t,k]
    keep = (pos < c).astype(x.dtype)

    # accumulate dispatch/combine over the small k axis to bound temps
    disp = jnp.zeros((b, t, e, c), x.dtype)
    comb = jnp.zeros((b, t, e, c), x.dtype)
    for kk in range(k):
        sel = (jax.nn.one_hot(gate_idx[:, :, kk], e, dtype=x.dtype)
               [:, :, :, None]
               * jax.nn.one_hot(pos[:, :, kk], c, dtype=x.dtype)
               [:, :, None, :]
               * keep[:, :, kk, None, None])
        disp = disp + sel
        comb = comb + sel * gate_w[:, :, kk, None, None].astype(x.dtype)

    # dispatch/combine accumulate in the activation dtype: every (e, c)
    # slot receives at most ONE nonzero term (one-hot selection), so the
    # low-precision psum is exact — and the cross-shard partial-sum
    # all-reduces halve vs XLA's default f32 accumulation (§Perf).
    xe = jnp.einsum("btec,btd->becd", disp, x,
                    preferred_element_type=x.dtype)         # a2a dispatch
    xe = shard(xe, "batch", "experts", None, "act_embed")
    ye = _expert_ffn(p, cfg, xe)
    y = jnp.einsum("btec,becd->btd", comb, ye,
                   preferred_element_type=x.dtype)          # a2a combine

    if m.n_shared:
        y = y + apply_mlp(p["shared"], x, "swiglu")
    if cfg.moe_tokens == "gathered":
        y = shard(y, "batch", "seq", "act_embed")           # RS back

    # Switch-style load-balancing aux loss
    me = probs.mean((0, 1))                                 # [e]
    ce = jax.nn.one_hot(gate_idx[..., 0], e).mean((0, 1))
    aux = m.router_aux_weight * e * jnp.sum(me * ce)
    return y, aux


def _expert_ffn(p: dict, cfg: ModelConfig, xe: jax.Array) -> jax.Array:
    """SwiGLU per expert: xe [b, e, c, d] -> [b, e, c, d]."""
    hi = jnp.einsum("becd,edf->becf", xe, p["wi"].astype(xe.dtype))
    hg = jnp.einsum("becd,edf->becf", xe, p["wg"].astype(xe.dtype))
    he = shard(jax.nn.silu(hg) * hi, "batch", "experts",
               None, "mlp")
    ye = jnp.einsum("becf,efd->becd", he, p["wo"].astype(xe.dtype))
    return shard(ye, "batch", "experts", None, "act_embed")


def _dispatch_sort(p: dict, cfg: ModelConfig, x, gate_w, gate_idx,
                   c: int) -> jax.Array:
    """§Perf sort-based dispatch: gather/scatter instead of one-hot
    einsums.  Same position-priority capacity semantics as the one-hot
    path (bitwise-matching drops), but the [b, t, e, c] dispatch tensors
    and their O(b·t·e·c·d) matmul flops disappear — compiled flops drop
    to ~6·N_active·D and the temp footprint to the gathered [b,e,c,d]."""
    m = cfg.moe
    b, t, d = x.shape
    e, k = m.n_experts, m.top_k
    tk = t * k

    def per_row(xr, widx, wval):
        # xr [t, d]; widx/wval [t, k]
        flat_e = widx.reshape(tk)                    # expert of each pair
        flat_w = wval.reshape(tk)
        flat_tok = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k)).reshape(tk)
        order = jnp.argsort(flat_e, stable=True)     # token-order stable
        se, stok, sw = flat_e[order], flat_tok[order], flat_w[order]
        # rank within expert segment = running index - segment start
        pos = jnp.arange(tk)
        seg_start = jnp.searchsorted(se, jnp.arange(e), side="left")
        rank = pos - seg_start[se]
        keep = rank < c
        slot = jnp.where(keep, se * c + rank, e * c)  # e*c = trash slot
        # gather tokens into [e*c, d] slots
        xe = jnp.zeros((e * c + 1, d), x.dtype).at[slot].set(
            jnp.where(keep[:, None], xr[stok], 0.0))[:e * c]
        return xe.reshape(e, c, d), slot, stok, sw, keep

    xe, slot, stok, sw, keep = jax.vmap(per_row)(x, gate_idx, gate_w)
    xe = shard(xe, "batch", "experts", None, "act_embed")
    ye = _expert_ffn(p, cfg, xe)                     # [b, e, c, d]

    def per_row_combine(ye_r, slot_r, stok_r, sw_r, keep_r):
        flat = ye_r.reshape(e * c, d)
        vals = jnp.where(keep_r[:, None],
                         flat[jnp.minimum(slot_r, e * c - 1)], 0.0)
        return jnp.zeros((t, d), x.dtype).at[stok_r].add(
            vals * sw_r[:, None].astype(x.dtype))

    return jax.vmap(per_row_combine)(ye, slot, stok, sw, keep)
