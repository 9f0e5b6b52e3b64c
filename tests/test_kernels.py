"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py),
executed in Pallas interpret mode on CPU (the kernel body runs in Python).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import elastic_matmul_op, flash_attention_op
from repro.kernels.ref import elastic_matmul_ref, flash_attention_ref

settings.register_profile("kernels", max_examples=8, deadline=None)
settings.load_profile("kernels")

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ka,na", [(256, 384), (128, 384), (256, 200),
                                   (100, 100), (1, 1), (129, 255)])
def test_elastic_matmul_sweep(dtype, ka, na):
    x = jax.random.normal(KEY, (64, 256), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (256, 384),
                          jnp.float32).astype(dtype)
    y = elastic_matmul_op(x, w, ka, na, bm=32, interpret=True)
    yr = elastic_matmul_ref(x, w, ka, na)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=tol, atol=tol)


@given(m=st.integers(1, 40), k_act=st.integers(1, 256),
       n_act=st.integers(1, 384))
def test_elastic_matmul_property(m, k_act, n_act):
    x = jax.random.normal(KEY, (m, 256))
    w = jax.random.normal(jax.random.fold_in(KEY, 2), (256, 384))
    y = elastic_matmul_op(x, w, k_act, n_act, bm=32,
                          interpret=True)
    yr = elastic_matmul_ref(x, w, k_act, n_act)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-4, atol=3e-4)
    assert np.all(np.asarray(y[:, n_act:]) == 0)


def test_elastic_matmul_traced_widths_one_executable():
    """The widths are traced: one jit covers every (k_act, n_act)."""
    x = jax.random.normal(KEY, (32, 256))
    w = jax.random.normal(KEY, (256, 256))
    f = jax.jit(lambda ka, na: elastic_matmul_op(x, w, ka, na, bm=32,
                                                 interpret=True))
    for ka, na in [(256, 256), (64, 128), (10, 250)]:
        np.testing.assert_allclose(
            np.asarray(f(ka, na)),
            np.asarray(elastic_matmul_ref(x, w, ka, na)),
            rtol=3e-4, atol=3e-4)


def test_elastic_matmul_needs_explicit_interpret_off_tpu():
    """On the CPU the kernel refuses to run unless interpret mode is asked
    for: nothing falls back to the Python interpreter in silence."""
    x = jnp.ones((32, 256))
    with pytest.raises(ValueError, match="interpret"):
        elastic_matmul_op(x, x.T, 256, 32, bm=32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S,T,H,KH,D", [
    (256, 256, 4, 4, 64), (256, 256, 4, 2, 64), (512, 512, 2, 1, 32),
])
def test_flash_attention_sweep(dtype, causal, S, T, H, KH, D):
    B = 2
    q = (jax.random.normal(KEY, (B, S, H, D), jnp.float32) * 0.3).astype(dtype)
    k = (jax.random.normal(jax.random.fold_in(KEY, 1), (B, T, KH, D),
                           jnp.float32) * 0.3).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, T, KH, D),
                          jnp.float32).astype(dtype)
    o = flash_attention_op(q, k, v, causal=causal, bq=128, bkv=128,
                           interpret=True)
    kr = jnp.repeat(k, H // KH, 2)
    vr = jnp.repeat(v, H // KH, 2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = kr.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    vf = vr.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    orf = flash_attention_ref(qf, kf, vf, causal=causal)
    orf = orf.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-3
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32), rtol=tol, atol=tol)


def test_flash_attention_long_context_block_sizes():
    """Non-square blocking + longer T (decode-ish asymmetry)."""
    q = jax.random.normal(KEY, (1, 128, 2, 64)) * 0.3
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 1024, 2, 64)) * 0.3
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 1024, 2, 64))
    o = flash_attention_op(q, k, v, causal=False, bq=64, bkv=256,
                           interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(2, 128, 64)
    kf = k.transpose(0, 2, 1, 3).reshape(2, 1024, 64)
    vf = v.transpose(0, 2, 1, 3).reshape(2, 1024, 64)
    orf = flash_attention_ref(qf, kf, vf, causal=False)
    orf = orf.reshape(1, 2, 128, 64).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=3e-3, atol=3e-3)
