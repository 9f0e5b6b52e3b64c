"""Mixture-of-Experts layer with three dispatch strategies.

* ``einsum`` — GShard/Switch-style one-hot dispatch.  Fully GSPMD-
  partitionable (experts on the ``model`` mesh axis, tokens on ``data``).
  Faithful baseline; its dispatch einsums are O(group_size) more FLOPs than
  the expert matmuls — the roofline analysis exposes this and the ``a2a``
  path removes it.
* ``a2a`` — production path: shard_map with sort-based token permutation
  and explicit ``all_to_all`` over the expert (model) axis, MaxText-style.
* ``dense`` — every expert on every token, combine by gate weight.  Only
  for tiny smoke tests and as the numerics oracle for the other two.

Elastic knobs (the paper's technique extended to MoE): ``a_experts``
restricts routing to the first n experts (masked or sliced), ``top_k`` and
``a_ff`` (per-expert hidden width) shrink compute.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.elastic import active_mask, take_dim
from repro.core.layers import dense_init, mlp_init, mlp_apply
from repro.core.types import is_static


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                     # per-expert hidden
    n_shared: int = 0             # shared (always-on) experts
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    group_size: int = 256         # einsum dispatch group
    dispatch: str = "einsum"      # einsum | a2a | dense
    expert_axis: str = "model"    # mesh axis experts are sharded over


def moe_init(key, d_model: int, cfg: MoEConfig, *, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 5)
    E, f = cfg.n_experts, cfg.d_ff
    s = 1.0 / math.sqrt(d_model)
    p = {
        "router": dense_init(ks[0], d_model, E, bias=False, dtype=jnp.float32),
        "wi": jax.random.normal(ks[1], (E, d_model, f), dtype) * s,
        "wg": jax.random.normal(ks[2], (E, d_model, f), dtype) * s,
        "wo": jax.random.normal(ks[3], (E, f, d_model), dtype) * (1.0 / math.sqrt(f)),
    }
    if cfg.n_shared:
        p["shared"] = mlp_init(ks[4], d_model, cfg.d_ff * cfg.n_shared,
                               gated=True, dtype=dtype)
    return p


def _router(p, x, cfg: MoEConfig, a_experts, top_k: int):
    """probs (..., E) fp32 with inactive experts masked out; top-k indices."""
    logits = (x.astype(jnp.float32) @ p["router"]["kernel"])
    E = cfg.n_experts
    if a_experts is not None:
        if is_static(a_experts) and int(a_experts) == E:
            pass
        else:
            neg = jnp.finfo(jnp.float32).min
            logits = jnp.where(jnp.arange(E) < a_experts, logits, neg)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)
    top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)
    return probs, top_vals, top_idx


def _aux_loss(probs, top_idx, cfg: MoEConfig):
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    E = cfg.n_experts
    f = jnp.mean(jax.nn.one_hot(top_idx, E, dtype=jnp.float32), axis=tuple(
        range(top_idx.ndim - 1)) + (top_idx.ndim - 1,))
    pbar = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    return E * jnp.sum(f * pbar)


def _expert_ffn(p, h, *, a_ff=None, slice_e=None):
    """h: (E, C, d) -> (E, C, d) SwiGLU per expert (einsum over stacked E)."""
    wi, wg, wo = p["wi"], p["wg"], p["wo"]
    if slice_e is not None:
        wi, wg, wo = wi[:slice_e], wg[:slice_e], wo[:slice_e]
    if a_ff is not None and is_static(a_ff):
        wi, wg, wo = wi[..., :a_ff], wg[..., :a_ff], wo[:, :a_ff]
    up = jnp.einsum("ecd,edf->ecf", h, wi.astype(h.dtype))
    gate = jnp.einsum("ecd,edf->ecf", h, wg.astype(h.dtype))
    hid = jax.nn.silu(gate) * up
    if a_ff is not None and not is_static(a_ff):
        hid = hid * active_mask(a_ff, hid.shape[-1], hid.dtype)
    return jnp.einsum("ecf,efd->ecd", hid, wo.astype(h.dtype))


# ---------------------------------------------------------------------------
# dense dispatch (oracle)
# ---------------------------------------------------------------------------

def _moe_dense(p, x, cfg, a_experts, top_k, a_ff):
    B, S, d = x.shape
    probs, top_vals, top_idx = _router(p, x, cfg, a_experts, top_k)
    E = cfg.n_experts
    toks = x.reshape(1, B * S, d).repeat(E, 0).reshape(E, B * S, d)
    outs = _expert_ffn(p, toks, a_ff=a_ff)                      # (E, BS, d)
    comb = jnp.zeros((B * S, E), jnp.float32)
    comb = comb.at[jnp.arange(B * S)[:, None],
                   top_idx.reshape(B * S, -1)].add(top_vals.reshape(B * S, -1))
    y = jnp.einsum("te,etd->td", comb.astype(x.dtype), outs)
    return y.reshape(B, S, d), _aux_loss(probs, top_idx, cfg)


# ---------------------------------------------------------------------------
# GShard einsum dispatch
# ---------------------------------------------------------------------------

def _moe_einsum(p, x, cfg, a_experts, top_k, a_ff, slice_e):
    B, S, d = x.shape
    # group over FLATTENED tokens: decode-style shapes (B x 1) form one
    # group of B tokens instead of B groups of 1, whose per-(group, expert)
    # capacity floor would pad expert compute ~E/top_k times.
    T = B * S
    g = min(cfg.group_size, T)
    while T % g:           # fall back to the largest divisor of T
        g -= 1
    G = T // g
    xg = x.reshape(G, g, d)
    probs, top_vals, top_idx = _router(p, xg, cfg, a_experts, top_k)
    E = cfg.n_experts if slice_e is None else slice_e
    if slice_e is not None:
        top_idx = jnp.minimum(top_idx, E - 1)   # indices already < E by masking
    # capacity always derives from the FULL expert count so that sliced and
    # masked sub-networks drop exactly the same tokens (slice == mask).
    C = max(4, int(math.ceil(g * top_k * cfg.capacity_factor / cfg.n_experts)))

    oh = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)           # (G,g,k,E)
    # position of each slot within its expert, counted over (token, k) slots
    ohf = oh.reshape(G, g * top_k, E)
    pos = (jnp.cumsum(ohf, axis=1) - ohf)                        # slots before
    loc = jnp.sum(pos * ohf, axis=-1).astype(jnp.int32)          # (G, g*k)
    keep = (loc < C).astype(jnp.float32).reshape(G, g, top_k)
    loc_oh = jax.nn.one_hot(loc.reshape(G, g, top_k), C, dtype=jnp.float32)
    gates = top_vals * keep                                      # (G,g,k)
    # combine (G,g,E,C) = sum_k gate_k * onehot_E * onehot_C
    combine = jnp.einsum("ngke,ngkc->ngec", oh * gates[..., None], loc_oh)
    combine = combine.astype(x.dtype)
    dispatch = (combine > 0).astype(x.dtype)
    ein = jnp.einsum("ngd,ngec->encd", xg, dispatch)             # (E,G,C,d)...
    expert_in = ein.reshape(E, G * C, d)
    expert_out = _expert_ffn(p, expert_in, a_ff=a_ff, slice_e=slice_e)
    expert_out = expert_out.reshape(E, G, C, d)
    y = jnp.einsum("ngec,encd->ngd", combine, expert_out)
    return y.reshape(B, S, d), _aux_loss(probs, top_idx, cfg)


# ---------------------------------------------------------------------------
# shard_map all-to-all dispatch (production EP)
# ---------------------------------------------------------------------------

def _moe_a2a_local(p_local, x_local, cfg: MoEConfig, a_experts, top_k, a_ff,
                   axis: str, n_shards: int):
    """Per-device body under shard_map.

    x_local: (T_loc, d) local tokens; p_local expert weights hold the local
    expert block (E_loc, d, f); router weights replicated.
    """
    T, d = x_local.shape
    E = cfg.n_experts
    E_loc = E // n_shards
    probs, top_vals, top_idx = _router(p_local, x_local, cfg, a_experts, top_k)
    # flatten (token, k) slots and sort by destination expert
    flat_e = top_idx.reshape(-1)                                  # (T*k,)
    flat_g = top_vals.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), top_k)
    order = jnp.argsort(flat_e)                                   # stable
    se, sg, st = flat_e[order], flat_g[order], flat_t[order]
    # position within expert after sort
    C = max(4, int(math.ceil(T * top_k * cfg.capacity_factor / E)))
    one = jax.nn.one_hot(se, E, dtype=jnp.int32)
    pos_in_e = (jnp.cumsum(one, axis=0) - one)[jnp.arange(se.shape[0]), se]
    keep = pos_in_e < C
    # send buffer (E, C, d); dropped tokens scatter to a scratch row
    send = jnp.zeros((E * C + 1, d), x_local.dtype)
    slot = jnp.where(keep, se * C + pos_in_e, E * C)
    send = send.at[slot].set(x_local[st])
    send = send[:-1].reshape(n_shards, E_loc * C, d)
    recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)       # (n, E_loc*C, d)
    recv = recv.reshape(n_shards, E_loc, C, d).transpose(1, 0, 2, 3) \
               .reshape(E_loc, n_shards * C, d)
    out = _expert_ffn(p_local, recv, a_ff=a_ff)                    # (E_loc, n*C, d)
    back = out.reshape(E_loc, n_shards, C, d).transpose(1, 0, 2, 3) \
              .reshape(n_shards, E_loc * C, d)
    got = jax.lax.all_to_all(back, axis, 0, 0, tiled=False)
    got = got.reshape(E * C, d)
    got = jnp.concatenate([got, jnp.zeros((1, d), got.dtype)], 0)
    slot_out = jnp.where(keep, se * C + pos_in_e, E * C)
    gathered = got[slot_out]                                       # (T*k, d)
    w = jnp.where(keep, sg, 0.0).astype(x_local.dtype)
    y = jnp.zeros((T, d), x_local.dtype).at[st].add(gathered * w[:, None])
    return y, _aux_loss(probs, top_idx, cfg)


def moe_apply(p: dict, x: jax.Array, cfg: MoEConfig, *,
              a_experts=None, top_k: Optional[int] = None, a_ff=None,
              a_model=None, mesh=None, data_axes=("data",)) -> tuple:
    """Returns (y (B,S,d), aux_loss).  Shared experts added on top."""
    top_k = top_k or cfg.top_k
    slice_e = None
    if a_experts is not None and is_static(a_experts) and int(a_experts) < cfg.n_experts:
        slice_e = int(a_experts)

    if cfg.dispatch == "dense":
        y, aux = _moe_dense(p, x, cfg, a_experts, top_k, a_ff)
    elif cfg.dispatch == "einsum" or mesh is None:
        y, aux = _moe_einsum(p, x, cfg, a_experts, top_k, a_ff, slice_e)
    elif cfg.dispatch == "a2a":
        B, S, d = x.shape
        ax = cfg.expert_axis
        n_shards = mesh.shape[ax]
        E = cfg.n_experts
        if S % n_shards:
            # decode-like shapes can't sequence-shard over the expert axis;
            # fall back to the einsum dispatch
            y, aux = _moe_einsum(p, x, cfg, a_experts, top_k, a_ff, slice_e)
            if "shared" in p:
                y = y + mlp_apply(p["shared"], x, a_model=a_model, a_ff=None)
            return y, aux

        def body(pr, pw, pg, po, xl):
            # xl: (B_loc, S/n_shards, d) — tokens split over the expert
            # axis too (sequence parallelism for the MoE block), so each
            # chip dispatches a distinct token slice and experts see their
            # true load instead of n_shards replicas.
            pl = {"router": {"kernel": pr}, "wi": pw, "wg": pg, "wo": po}
            xf = xl.reshape(-1, d)
            y, aux = _moe_a2a_local(pl, xf, cfg, a_experts, top_k, a_ff,
                                    ax, n_shards)
            return y.reshape(xl.shape), jnp.array([[aux]])  # keep shard dims

        batch_spec = P(tuple(data_axes), ax, None)
        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None), P(ax, None, None), P(ax, None, None),
                      P(ax, None, None), batch_spec),
            out_specs=(batch_spec, P(tuple(data_axes), ax)),
            check_vma=False,
        )(p["router"]["kernel"], p["wi"], p["wg"], p["wo"], x)
        aux = jnp.mean(aux)
    else:
        raise ValueError(cfg.dispatch)

    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, a_model=a_model, a_ff=None)
    return y, aux
