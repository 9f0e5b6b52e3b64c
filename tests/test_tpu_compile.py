"""Compile the serving path at real size for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a ``v5e:2x2`` topology that is only described.  Nothing runs, so these
tests say nothing about results or times; they catch what the chip's
compiler refuses (tiling, fast-memory limits, shapes) before a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.  Keep every such compile in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.types import SubnetSpec
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import elastic_matmul_op
from repro.models.vit import vit_apply, vit_init
from repro.runtime.engine import DynamicServer


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without the chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_elastic_matmul_compiles_to_mosaic(one_chip):
    fn = jax.jit(functools.partial(elastic_matmul_op, interpret=False))
    compiled = fn.lower(_sds((2048, 384), jnp.bfloat16, one_chip),
                        _sds((384, 1536), jnp.bfloat16, one_chip),
                        _sds((), jnp.int32, one_chip),
                        _sds((), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_non_causal(one_chip):
    fn = jax.jit(functools.partial(flash_attention, causal=False))
    q = _sds((48, 256, 64), jnp.bfloat16, one_chip)
    compiled = fn.lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bucket", [1, 8])
@pytest.mark.parametrize("which", ["max_spec", "min_spec"])
def test_supernet_executable_compiles(one_chip, which, bucket):
    """The serving engine's own (subnet, bucket) executable at
    ``make_config`` widths."""
    cfg = get_arch("dynamic-ofa-supernet").make_config()
    dims = {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
            "n_heads": cfg.n_heads, "n_layers": cfg.n_layers}
    server = DynamicServer(lambda p, x, E: vit_apply(p, x, cfg, E=E)[0],
                           None, dims, max_batch=8)
    spec: SubnetSpec = getattr(cfg.elastic, which)()
    params = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(functools.partial(vit_init, cfg=cfg),
                       jax.random.PRNGKey(0)))
    x = _sds((bucket, cfg.img_res, cfg.img_res, 3), jnp.float32, one_chip)
    compiled = server.executable(spec, bucket).lower(params, x).compile()
    out = compiled.out_info
    assert out.shape == (bucket, cfg.n_classes)
    assert out.dtype == cfg.cdtype()
