"""jit'd public wrappers around the Pallas kernels (padding + reshaping).

The kernels compile to Mosaic for a TPU.  ``interpret=True`` runs the
kernel body in Python instead, on any backend; callers without a TPU
(tests, CPU examples) must ask for it explicitly, so a missing chip fails
loudly instead of silently running the slow interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.elastic_matmul import elastic_matmul
from repro.kernels.flash_attention import flash_attention


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def elastic_matmul_op(x, w, k_act, n_act, *, bm=128, bk=128, bn=128,
                      interpret: bool = False):
    """Batched elastic matmul: x (..., K) @ w (K, N) with runtime widths."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm_eff = min(bm, max(8, M))
    x2 = _pad_to(_pad_to(x2, 0, bm_eff), 1, bk)
    w2 = _pad_to(_pad_to(w, 0, bk), 1, bn)
    y = elastic_matmul(x2, w2.astype(x.dtype),
                       jnp.asarray(k_act, jnp.int32),
                       jnp.asarray(n_act, jnp.int32),
                       bm=bm_eff, bk=bk, bn=bn, interpret=interpret)
    return y[:M, :N].reshape(lead + (N,))


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bkv", "interpret"))
def flash_attention_op(q, k, v, *, causal=True, bq=256, bkv=256,
                       interpret: bool = False):
    """q (B, S, H, D), k/v (B, T, KH, D) -> (B, S, H, D). GQA repeats kv."""
    B, S, H, D = q.shape
    _, T, KH, _ = k.shape
    if KH != H:
        assert H % KH == 0
        rep = H // KH
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    bq_eff = min(bq, S)
    bkv_eff = min(bkv, T)
    qf = _pad_to(qf, 1, bq_eff)
    kf = _pad_to(kf, 1, bkv_eff)
    vf = _pad_to(vf, 1, bkv_eff)
    # NOTE: padding keys would corrupt softmax for non-divisible T in the
    # non-causal case; assignment shapes are powers of two so exact here.
    o = flash_attention(qf, kf, vf, causal=causal, bq=bq_eff, bkv=bkv_eff,
                        interpret=interpret)
    o = o[:, :S].reshape(B, H, S, D).transpose(0, 2, 1, 3)
    return o
