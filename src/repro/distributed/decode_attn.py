"""Distributed decode attention: two-pass softmax over a sequence-sharded
KV cache (shard_map + pmax/psum).

Baseline finding (§Perf): with the 32k KV cache sequence-sharded over the
``model`` axis, GSPMD lowers one-token decode attention by ALL-GATHERING
the cache (granite-20b: 5 GB/step/device; qwen: 0.55 s collective term).
The classic fix is to keep the cache in place and reduce softmax
statistics instead:

  pass 1: local scores + local max  -> pmax  (B,R,K floats)
  pass 2: local exp-sums + local PV -> psum  (B,R,K + B,R,K,D floats)

Collective bytes drop from O(T·K·D) to O(R·K·D) per token — about four
orders of magnitude for 32k contexts.  The cache update (dynamic-update-
slice at the decode index) also becomes fully local: only the shard owning
the write position updates.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def sharded_decode_attention(q, k_new, v_new, cache_k, cache_v, cache_len, *,
                             mesh, seq_axes=("model",),
                             batch_axes: Tuple[str, ...] = ("pod", "data")):
    """One decode step against a sequence-sharded cache.

    q:       (B, 1, R, K, D)  new-token queries (RoPE applied), replicated
                              over ``seq_axes``
    k_new:   (B, 1, K, D)     new key/value (RoPE applied)
    cache_k: (B, T, K, D)     T sharded over ``seq_axes`` (one or several
                              mesh axes, row-major)
    cache_len: int32 scalar   write position (new token lands here)

    Returns (out (B,1,R,K,D), new_cache_k, new_cache_v).
    """
    D = q.shape[-1]
    scale = 1.0 / math.sqrt(D)
    seq_axes = tuple(a for a in seq_axes if a in mesh.axis_names)
    b_axes = tuple(a for a in batch_axes
                   if a in mesh.axis_names and a not in seq_axes)
    bspec = b_axes if b_axes else None

    def body(q, kn, vn, ck, cv, clen):
        T_loc = ck.shape[1]
        shard = jnp.zeros((), jnp.int32)
        for a in seq_axes:                       # row-major flat shard index
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        start = shard * T_loc
        # --- local cache write (no cross-shard traffic) -------------------
        idx = clen - start
        in_range = jnp.logical_and(idx >= 0, idx < T_loc)
        safe = jnp.clip(idx, 0, T_loc - 1)
        kn_w = jnp.where(in_range, kn.astype(ck.dtype),
                         jax.lax.dynamic_slice(ck, (0, safe, 0, 0),
                                               kn.shape))
        vn_w = jnp.where(in_range, vn.astype(cv.dtype),
                         jax.lax.dynamic_slice(cv, (0, safe, 0, 0),
                                               vn.shape))
        ck = jax.lax.dynamic_update_slice(ck, kn_w, (0, safe, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, vn_w, (0, safe, 0, 0))
        # --- two-pass softmax ---------------------------------------------
        q0 = q[:, 0].astype(jnp.float32)                    # (B,R,K,D)
        s = jnp.einsum("brkd,btkd->brkt", q0,
                       ck.astype(jnp.float32)) * scale       # (B,R,K,T_loc)
        pos = start + jnp.arange(T_loc)
        s = jnp.where(pos <= clen, s, jnp.finfo(jnp.float32).min)
        m_loc = jnp.max(s, axis=-1)
        m_g = jax.lax.pmax(m_loc, seq_axes)                  # pass 1
        p = jnp.exp(s - m_g[..., None])
        l_loc = jnp.sum(p, axis=-1)
        pv_loc = jnp.einsum("brkt,btkd->brkd", p,
                            cv.astype(jnp.float32))
        l_g = jax.lax.psum(l_loc, seq_axes)                  # pass 2
        pv_g = jax.lax.psum(pv_loc, seq_axes)
        out = (pv_g / jnp.maximum(l_g[..., None], 1e-30))[:, None]
        return out.astype(q.dtype), ck, cv

    cache_spec = P(bspec, seq_axes, None, None)
    rep4 = P(bspec, None, None, None)
    out, ck, cv = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None, None, None), rep4, rep4,
                  cache_spec, cache_spec, P()),
        out_specs=(P(bspec, None, None, None, None), cache_spec, cache_spec),
        check_vma=False,
    )(q, k_new, v_new, cache_k, cache_v, cache_len)
    return out, ck, cv
