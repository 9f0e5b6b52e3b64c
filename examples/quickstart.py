"""Quickstart: the paper's idea in 60 lines.

Builds a dynamic ViT supernet, extracts three sub-networks, shows that
(1) sliced and masked execution agree, (2) smaller sub-networks are
genuinely faster, (3) the elastic Pallas kernel matches its oracle.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.elastic import spec_to_dynamic, spec_to_static
from repro.core.types import SubnetSpec
from repro.models.vit import vit_apply, vit_init

arch = get_arch("dynamic-ofa-supernet")
cfg = arch.make_smoke()
params = vit_init(jax.random.PRNGKey(0), cfg)
dims = {"d_model": cfg.d_model, "d_ff": cfg.d_ff, "n_heads": cfg.n_heads,
        "n_layers": cfg.n_layers}
x = np.random.default_rng(0).normal(
    size=(8, cfg.img_res, cfg.img_res, 3)).astype(np.float32)

print(f"supernet: {cfg.name}  ({cfg.n_layers}L d={cfg.d_model})")
print(f"elastic space: {len(cfg.elastic.enumerate())} sub-networks\n")

for spec in [SubnetSpec(),
             SubnetSpec(width_mult=0.5, ffn_mult=0.5),
             SubnetSpec(width_mult=0.5, ffn_mult=0.25, depth_mult=2 / 3)]:
    E_static = spec_to_static(spec, dims)
    E_masked = spec_to_dynamic(spec, dims)

    sliced = jax.jit(lambda p, x: vit_apply(p, x, cfg, E=E_static)[0])
    masked = jax.jit(lambda p, x, E: vit_apply(p, x, cfg, E=E)[0])

    y_s = jax.block_until_ready(sliced(params, x))
    y_m = jax.block_until_ready(masked(params, x, E_masked))
    agree = np.allclose(np.asarray(y_s), np.asarray(y_m), atol=5e-3)

    t0 = time.perf_counter()
    for _ in range(10):
        jax.block_until_ready(sliced(params, x))
    ms = (time.perf_counter() - t0) / 10 * 1e3
    print(f"{spec.name():24s} latency={ms:6.2f}ms  sliced==masked: {agree}")

# the elastic Pallas kernel (TPU target, interpret-mode here)
from repro.kernels.ops import elastic_matmul_op
from repro.kernels.ref import elastic_matmul_ref

xm = jax.random.normal(jax.random.PRNGKey(1), (128, 512))
wm = jax.random.normal(jax.random.PRNGKey(2), (512, 512))
y = elastic_matmul_op(xm, wm, 256, 384, interpret=True)
yr = elastic_matmul_ref(xm, wm, 256, 384)
print(f"\nelastic_matmul kernel vs oracle: "
      f"max_err={float(jnp.max(jnp.abs(y - yr))):.2e}")
