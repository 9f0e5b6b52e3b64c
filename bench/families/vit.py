"""The ViT / DeiT family: everything the harness knows of such a model.

A configuration names its family (``"family": "vit"``), and the harness
reads all that is particular to the model through this file
(``bench/families/<family>.py``); a model of another family is a file
beside this one with the same names, plus its configuration and data.
The names (``harness.FAMILY_API``):

* ``model_of(cfg)`` -- the ``model`` block the program builds from its
  configuration object, compared with the configuration file's;
* ``make_weights(model, seed32, device)`` -- seeded weights, made on the
  device in one jitted call, in the layout the serving code takes;
  ``check_layout(ours, theirs)`` compares them leaf for leaf with the
  server's own before they are swapped in;
* ``pool(model, n, rng)`` -- ``n`` seeded request payloads, one per row;
* ``reference(w, xs, model, subnet, fp8=False)`` -- the plain reference's
  answers to the payloads ``xs`` in the served layout, and with
  ``fp8=True`` the lower-precision control; ``rel_err(answers, refs)``
  -- one error per answer, the number ``correct`` is judged by;
* ``flops_per_request(model, subnet)`` and ``roofline_s(model, subnet,
  bucket, peak)`` -- operations of one request, and the least time of
  one call on a padded batch of ``bucket``;
* ``spec(subnet)`` -- the program's ``SubnetSpec`` for a sub-network
  name; ``FULL`` -- the name of the whole model.

Sub-networks.  A served sub-network is named
``w<width>-f<ffn>-h<heads>-d<depth>``: each multiplier scales the full
model's residual width, feed-forward width, query heads and layer count,
rounded to the nearest whole unit (at least one, at most the full size).
FLOP counts, the reference and the accuracy surrogate all derive the
sizes here, apart from the serving code.

Weights.  The tree has the layout the serving code's ViT takes
(``patch_embed``, ``cls``, ``pos``, ``layers`` stacked over depth,
``final_ln``, ``head``, ``head_dist`` for a distillation token,
``exit_heads``).  Kernels are drawn N(0, 1/fan_in), biases and layer-norm
offsets N(0, 0.02^2), layer-norm scales 1 + N(0, 0.02^2), class/position
tokens N(0, 0.02^2), so every parameter, biases included, shapes the
output.  Payloads are float32 images, N(0, 1) per pixel.

Reference.  A plain float32 ViT / DeiT forward, written from the
published architecture (Dosovitskiy et al. 2021; DeiT, Touvron et al.
2021) and apart from the serving code: images are cut into patches and
embedded, a class token (and DeiT's distillation token) is put in front,
position embeddings are added, then pre-norm blocks of multi-head
self-attention and a GELU MLP, a final layer norm, and the class head on
the class token.  A sub-network takes the first ``d_model`` channels of
the residual stream and of every weight that reads or writes it, the
first ``heads`` attention heads, the first ``d_ff`` MLP units and the
first ``layers`` blocks.  Departures from the published models, each
shared with what is served: the output is the class head alone (DeiT
averages it with the distillation head), and layer norms use eps 1e-6.
GELU is the exact (erf) form, as published.  ``fp8=True`` is the control:
the same forward with both operands of every matrix product rounded to
float8 e4m3 under a per-tensor scale, the precision step below the
bfloat16 the configurations serve in.

Operations and bytes.  FLOPs count matrix multiplications only, two per
multiply-add: the patch embedding, the q/k/v/o projections, the two
attention products (QK^T and AV: 2*T*T*h*d_head each), the two MLP
layers, and the class head on the class token.  Layer norms, softmax,
GELU and bias adds are left out; they are under 1% of the total at these
widths.  Bytes are the compulsory HBM traffic of one call: every active
weight read once at its stored precision, the float32 input batch, and
the bfloat16 logits written back.  Activations are left out, so the
bound they give is a lower bound on the time, as a roofline needs.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

FULL = "w1-f1-h1-d1"

MODEL_KEYS = ("img_res", "patch", "n_layers", "d_model", "n_heads", "d_ff",
              "n_classes", "distill_token", "exit_layers", "param_dtype",
              "compute_dtype")


def model_of(cfg) -> dict:
    """The ``model`` block of a program configuration object."""
    return {k: getattr(cfg, k) for k in MODEL_KEYS}


# --- sub-networks ------------------------------------------------------------

KNOBS = {"w": "width", "f": "ffn", "h": "heads", "d": "depth"}


def parse(name: str) -> Dict[str, float]:
    """``"w0.75-f0.5-h0.75-d0.5"`` -> {"width": 0.75, ...}."""
    out = {}
    for part in name.split("-"):
        key = KNOBS.get(part[:1])
        if key is None or key in out:
            raise ValueError(f"not a sub-network name: {name!r}")
        out[key] = float(part[1:])
    if set(out) != set(KNOBS.values()):
        raise ValueError(f"not a sub-network name: {name!r}")
    return out


def _scale(full: int, mult: float) -> int:
    return min(full, max(1, int(round(full * mult))))


def dims(model: dict, name: str) -> Dict[str, int]:
    """Active sizes of sub-network ``name`` of ``model`` (a config's
    ``model`` block): d_model, d_ff, heads, layers, plus the fixed head
    size and token count."""
    m = parse(name)
    d_head = model["d_model"] // model["n_heads"]
    n_special = 2 if model.get("distill_token") else 1
    return {
        "d_model": _scale(model["d_model"], m["width"]),
        "d_ff": _scale(model["d_ff"], m["ffn"]),
        "heads": _scale(model["n_heads"], m["heads"]),
        "layers": _scale(model["n_layers"], m["depth"]),
        "d_head": d_head,
        "tokens": (model["img_res"] // model["patch"]) ** 2 + n_special,
    }


def spec(name: str):
    """The serving code's ``SubnetSpec`` of sub-network ``name``."""
    from repro.core.types import SubnetSpec
    m = parse(name)
    return SubnetSpec(width_mult=m["width"], ffn_mult=m["ffn"],
                      heads_mult=m["heads"], depth_mult=m["depth"])


# --- weights and payloads ----------------------------------------------------

def _tree(key, model: dict):
    d, f, c = model["d_model"], model["d_ff"], model["n_classes"]
    p, L = model["patch"], model["n_layers"]
    n_special = 2 if model.get("distill_token") else 1
    tokens = (model["img_res"] // p) ** 2 + n_special
    keys = iter(jax.random.split(key, 64))

    def normal(shape, std):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    def dense(d_in, d_out, lead=()):
        return {"kernel": normal(lead + (d_in, d_out), 1 / math.sqrt(d_in)),
                "bias": normal(lead + (d_out,), 0.02)}

    def norm(n, lead=()):
        return {"scale": 1.0 + normal(lead + (n,), 0.02),
                "bias": normal(lead + (n,), 0.02)}

    layer = (L,)
    tree = {
        "patch_embed": {"kernel": normal((p, p, 3, d),
                                         1 / math.sqrt(p * p * 3)),
                        "bias": normal((d,), 0.02)},
        "cls": normal((n_special, d), 0.02),
        "pos": normal((tokens, d), 0.02),
        "final_ln": norm(d),
        "head": dense(d, c),
        "layers": {
            "ln1": norm(d, layer),
            "attn": {k: dense(d, d, layer) for k in ("q", "k", "v", "o")},
            "ln2": norm(d, layer),
            "mlp": {"wi": dense(d, f, layer), "wo": dense(f, d, layer)},
        },
    }
    if model.get("distill_token"):
        tree["head_dist"] = dense(d, c)
    if model.get("exit_layers"):
        tree["exit_heads"] = [dense(d, c) for _ in model["exit_layers"]]
    dtype = jnp.dtype(model["param_dtype"])
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)


def make_weights(model: dict, seed32: int, device) -> dict:
    """The weights of ``model`` (a config's ``model`` block) from a 32-bit
    seed, computed on ``device`` by one jitted call."""
    fn = jax.jit(lambda k: _tree(k, model),
                 out_shardings=jax.sharding.SingleDeviceSharding(device))
    return fn(jax.random.PRNGKey(seed32))


def check_layout(ours, theirs) -> None:
    """Raise unless both trees have the same structure, shapes and dtypes."""
    s_ours = jax.tree_util.tree_structure(ours)
    s_theirs = jax.tree_util.tree_structure(theirs)
    if s_ours != s_theirs:
        raise SystemExit(f"bench weights: tree {s_ours} is not the "
                         f"server's {s_theirs}")
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise SystemExit(f"bench weights: leaf {a.shape} {a.dtype} is "
                             f"not the server's {b.shape} {b.dtype}")


def pool(model: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` float32 images of the model's resolution, N(0, 1) pixels."""
    res = model["img_res"]
    return rng.standard_normal((n, res, res, 3), np.float32)


# --- the plain reference -----------------------------------------------------

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(a):
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / E4M3_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _norm(x, p, n, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"][:n] + p["bias"][:n]


def forward(w, images, model: dict, name: str, fp8: bool = False):
    """Logits (B, n_classes) of sub-network ``name`` on float32 images."""
    s = dims(model, name)
    a, f, hh, dh = s["d_model"], s["d_ff"], s["heads"], s["d_head"]
    hd = hh * dh
    p = model["patch"]
    B, H, W, C = images.shape
    x = images.astype(jnp.float32)
    patches = x.reshape(B, H // p, p, W // p, p, C).transpose(0, 1, 3, 2, 4, 5)
    patches = patches.reshape(B, (H // p) * (W // p), p * p * C)
    kernel = w["patch_embed"]["kernel"].reshape(p * p * C, -1)[:, :a]
    h = _mm("bnk,kd->bnd", patches, kernel, fp8) + w["patch_embed"]["bias"][:a]
    special = jnp.broadcast_to(w["cls"][None, :, :a],
                               (B,) + w["cls"][:, :a].shape)
    h = jnp.concatenate([special, h], axis=1) + w["pos"][None, :, :a]
    T = h.shape[1]
    for i in range(s["layers"]):
        lw = jax.tree_util.tree_map(lambda t: t[i], w["layers"])
        at = lw["attn"]
        hn = _norm(h, lw["ln1"], a)
        q, k, v = (_mm("btd,de->bte", hn, at[n]["kernel"][:a, :hd], fp8)
                   + at[n]["bias"][:hd] for n in ("q", "k", "v"))
        q, k, v = (t.reshape(B, T, hh, dh) for t in (q, k, v))
        scores = _mm("bqhd,bkhd->bhqk", q, k, fp8) / math.sqrt(dh)
        probs = jax.nn.softmax(scores, axis=-1)
        o = _mm("bhqk,bkhd->bqhd", probs, v, fp8).reshape(B, T, hd)
        h = h + _mm("bte,ed->btd", o, at["o"]["kernel"][:hd, :a], fp8) \
            + at["o"]["bias"][:a]
        hn = _norm(h, lw["ln2"], a)
        m = lw["mlp"]
        u = _mm("btd,df->btf", hn, m["wi"]["kernel"][:a, :f], fp8) \
            + m["wi"]["bias"][:f]
        u = jax.nn.gelu(u, approximate=False)
        h = h + _mm("btf,fd->btd", u, m["wo"]["kernel"][:f, :a], fp8) \
            + m["wo"]["bias"][:a]
    cls = _norm(h, w["final_ln"], a)[:, 0]
    return _mm("bd,dc->bc", cls, w["head"]["kernel"][:a], fp8) \
        + w["head"]["bias"]


@functools.lru_cache(maxsize=None)
def _jitted(model_key: str, name: str, fp8: bool):
    model = json.loads(model_key)
    return jax.jit(lambda w, x: forward(w, x, model, name, fp8))


def reference(w, images, model: dict, name: str, *, fp8: bool = False,
              block: int = 16) -> np.ndarray:
    """``forward`` over ``images`` in blocks of ``block`` rows (the last
    one padded), so one compiled shape serves any sample size."""
    fn = _jitted(json.dumps(model, sort_keys=True), name, fp8)
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(images), block):
            chunk = np.asarray(images[i:i + block], np.float32)
            n = len(chunk)
            if n < block:
                chunk = np.concatenate(
                    [chunk, np.zeros((block - n,) + chunk.shape[1:],
                                     np.float32)])
            out.append(np.asarray(fn(w, chunk))[:n])
    return np.concatenate(out)


def rel_err(y, ref) -> np.ndarray:
    """Per answer (a row of logits): largest |served - reference| over
    the largest |reference|."""
    y = np.asarray(y, np.float32)
    ref = np.asarray(ref, np.float32)
    den = np.maximum(np.max(np.abs(ref), axis=-1), 1e-6)
    return np.max(np.abs(y - ref), axis=-1) / den


# --- operations and bytes ----------------------------------------------------

PARAM_BYTES = {"float32": 4, "bfloat16": 2}
OUT_BYTES = {"float32": 4, "bfloat16": 2}


def flops_per_request(model: dict, name: str) -> float:
    """Matmul FLOPs to serve one image with sub-network ``name``."""
    s = dims(model, name)
    a, f, t = s["d_model"], s["d_ff"], s["tokens"]
    hd = s["heads"] * s["d_head"]
    p = model["patch"]
    n_patches = (model["img_res"] // p) ** 2
    per_layer = (2 * t * a * hd * 3       # q, k, v projections
                 + 2 * t * t * hd * 2     # QK^T and AV
                 + 2 * t * hd * a         # output projection
                 + 2 * t * a * f * 2)     # MLP in and out
    patch = 2 * n_patches * p * p * 3 * a
    head = 2 * a * model["n_classes"]
    return float(s["layers"] * per_layer + patch + head)


def param_count(model: dict, name: str) -> int:
    """Weights that sub-network ``name`` reads (its slice of each leaf)."""
    s = dims(model, name)
    a, f = s["d_model"], s["d_ff"]
    hd = s["heads"] * s["d_head"]
    p = model["patch"]
    n_special = 2 if model.get("distill_token") else 1
    per_layer = (2 * a                    # ln1
                 + 3 * (a * hd + hd)      # q, k, v with bias
                 + hd * a + a             # o with bias
                 + 2 * a                  # ln2
                 + a * f + f + f * a + a)  # MLP with biases
    embed = p * p * 3 * a + a + n_special * a + s["tokens"] * a
    head = 2 * a + a * model["n_classes"] + model["n_classes"]
    return s["layers"] * per_layer + embed + head


def hbm_bytes(model: dict, name: str, bucket: int) -> float:
    """Compulsory HBM bytes of one call on a padded batch of ``bucket``."""
    image = model["img_res"] ** 2 * 3 * 4          # float32 pixels
    logits = model["n_classes"] * OUT_BYTES[model["compute_dtype"]]
    weights = param_count(model, name) * PARAM_BYTES[model["param_dtype"]]
    return float(weights + bucket * (image + logits))


def roofline_s(model: dict, name: str, bucket: int, peak: dict):
    """(least seconds for one call, which bound sets it) on a chip whose
    peaks are ``peak`` (a row of peaks.json)."""
    t_flops = bucket * flops_per_request(model, name) \
        / peak["bf16_flops_per_s"]
    t_bytes = hbm_bytes(model, name, bucket) / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
