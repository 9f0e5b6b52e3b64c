"""The plain reference agrees with the serving code's ViT on small sizes,
full width and sliced, and the float8 control does not."""
import dataclasses

import jax
import numpy as np
import pytest

from bench.families import vit

# full width, and one that cuts every knob
SUBNETS = ["w1-f1-h1-d1", "w0.5-f0.5-h0.5-d0.5"]


@pytest.fixture(scope="module", params=["dynamic-ofa-supernet", "deit-b"])
def smoke(request):
    from repro.configs import get_arch
    cfg = get_arch(request.param).make_smoke()
    model = vit.model_of(cfg)
    w = vit.make_weights(model, 7, jax.devices()[0])
    x = np.random.default_rng(3).normal(
        size=(5, cfg.img_res, cfg.img_res, 3)).astype(np.float32)
    return cfg, model, w, x


def served(cfg, w, x, name):
    from repro.core.elastic import spec_to_static
    from repro.models.vit import vit_apply
    spec = vit.spec(name)
    dims = {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
            "n_heads": cfg.n_heads, "n_layers": cfg.n_layers}
    E = spec_to_static(spec, dims)
    return np.asarray(vit_apply(w, x, cfg, E=E)[0])


@pytest.mark.parametrize("name", SUBNETS)
def test_reference_matches_vit_apply(smoke, name):
    cfg, model, w, x = smoke
    from repro.models.vit import vit_init
    vit.check_layout(w, vit_init(jax.random.PRNGKey(0), cfg))
    ref = vit.reference(w, x, model, name, block=4)
    err = vit.rel_err(served(cfg, w, x, name), ref)
    # float32 both sides; the serving code's tanh GELU is the only
    # difference in the arithmetic
    assert err.max() < 2e-3, err
    # and the next row's answer is far off
    assert vit.rel_err(np.roll(ref, 1, 0), ref).min() > 0.1


def test_fp8_control_reads_far_above_bf16(smoke):
    cfg, model, w, x = smoke
    name = SUBNETS[0]
    ref = vit.reference(w, x, model, name)
    bf16 = served(dataclasses.replace(cfg, compute_dtype="bfloat16"), w, x,
                  name)
    ctrl = vit.reference(w, x, model, name, fp8=True)
    sound = vit.rel_err(bf16, ref).max()
    control = vit.rel_err(ctrl, ref).max()
    assert control > 3 * sound, (sound, control)
