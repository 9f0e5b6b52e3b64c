"""Kernel-level benchmark: elastic-width compute scaling.

On this CPU container the Pallas kernels run in interpret mode (timing is
meaningless for TPU), so the wall-clock rows come from the XLA sliced path
— demonstrating that sub-network compute genuinely shrinks — and the
kernel rows report correctness + the analytic MXU-work ratio the elastic
kernel achieves by skipping dead tiles.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import elastic_matmul_op
from repro.kernels.ref import elastic_matmul_ref


def _time(fn, *args, iters=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run():
    M, K, N = 512, 1024, 1024
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (M, K))
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N))
    rows = []

    # XLA sliced matmuls: compute scales ~quadratically with width
    for frac in (1.0, 0.75, 0.5, 0.25):
        ka, na = int(K * frac), int(N * frac)
        f = jax.jit(lambda a, b: a @ b)
        us = _time(f, x[:, :ka], w[:ka, :na])
        rows.append((f"kernel/xla_sliced_w{frac:g}", us,
                     f"{ka}x{na} of {K}x{N}"))

    # elastic kernel: correctness + tile-skip work ratio
    for frac in (1.0, 0.5, 0.25):
        ka, na = int(K * frac), int(N * frac)
        y = elastic_matmul_op(x, w, ka, na, interpret=True)
        yr = elastic_matmul_ref(x, w, ka, na)
        err = float(jnp.max(jnp.abs(y - yr)))
        live_tiles = -(-ka // 128) * -(-na // 128)
        total_tiles = (K // 128) * (N // 128)
        rows.append((f"kernel/elastic_w{frac:g}_tile_work",
                     100.0 * live_tiles / total_tiles,
                     f"% of MXU tiles live; max_err={err:.2e}"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(c) for c in r))
