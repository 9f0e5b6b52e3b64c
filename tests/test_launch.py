"""Entry points fail loudly (no chip, a failed batch, a misplaced cache)
and place what they build: replicas on their own devices, LUT timings
with the batch already on the device."""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.core.types import ElasticSpace, SubnetSpec
from repro.launch import cache, serve
from repro.models.vit import ViTConfig
from repro.runtime.engine import DynamicServer

REPO = Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_cpu():
    """No TPU: non-zero exit and no result line."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         cwd=REPO, env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no TPU" in res.stderr


def _tiny_arch():
    """``dynamic-ofa`` smoke arch cut to two subnets and batch 2, so
    ``serve.main`` measures and warms in seconds."""
    arch = get_arch("dynamic-ofa-supernet")
    cfg = ViTConfig(name="dynamic-ofa-tiny", img_res=16, patch=8,
                    n_layers=2, d_model=32, n_heads=4, d_ff=64, n_classes=4,
                    compute_dtype="float32",
                    elastic=ElasticSpace(width_mults=(0.5, 1.0),
                                         ffn_mults=(1.0,), heads_mults=(1.0,),
                                         depth_mults=(1.0,)))
    return dataclasses.replace(arch, make_smoke=lambda: cfg)


@pytest.mark.parametrize("stage", ["_dispatch", "_complete"])
def test_serve_main_fails_on_failed_requests(monkeypatch, capsys, tmp_path,
                                             stage):
    """A dispatch or batch that raises answers its futures with an error
    payload (the server keeps serving); the launcher must still exit
    non-zero instead of reporting latencies over error payloads."""
    def boom(self, *a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setenv(cache.ENV, str(tmp_path))   # keep the checkout's
    monkeypatch.setattr(serve, "get_arch", lambda _: _tiny_arch())
    monkeypatch.setattr(DynamicServer, stage, boom)
    with pytest.raises(SystemExit) as exc:
        serve.main(["--smoke", "--requests", "4", "--trace-steps", "2",
                    "--max-batch", "2"])
    assert exc.value.code not in (0, None)
    assert "requests failed" in str(exc.value.code)
    assert "device:" in capsys.readouterr().out


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv(cache.ENV, raising=False)
    path = cache.use_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_respects_env(monkeypatch, cache_dir_config, tmp_path):
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # JAX reads the env


def test_compile_cache_env_dir_gets_the_entries(tmp_path):
    """End to end in a fresh process: entries land in the env's directory."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    code = ("import jax; from repro.launch.cache import use_compile_cache; "
            "use_compile_cache(); print(jax.jit(lambda x: x * 2)(3.0))")
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    assert any((tmp_path / "cc").iterdir())


def test_serve_nodes_put_each_node_on_its_own_device(subproc):
    """``--nodes 2`` on a two-device host: both of each class's replicas
    exist, and node i's params sit on device i alone."""
    out = subproc(f"""
import sys
sys.path.insert(0, {str(REPO / "tests")!r})
import jax
from repro.launch import serve
from test_launch import _tiny_arch
built, real = [], serve.build_server
def spy(*a, **k):
    s = real(*a, **k)
    built.append((k.get("tenant"), sorted(
        {{d.id for leaf in jax.tree_util.tree_leaves(s.params)
          for d in leaf.devices()}})))
    return s
serve.build_server = spy
serve.get_arch = lambda _: _tiny_arch()
serve.main(["--smoke", "--trace", "poisson", "--nodes", "2",
            "--trace-duration", "0.5", "--requests", "4",
            "--max-batch", "2"])
print("BUILT", sorted(b for b in built if b[0] is not None))
""", n_devices=2)
    built = out.split("BUILT", 1)[1].strip()
    assert built == str(sorted(
        (t, [i]) for t in ("batch", "interactive") for i in (0, 1)))


def test_measure_times_the_batch_on_the_params_device(monkeypatch):
    """The LUT timing runs on a device-resident batch, so the input copy
    from host memory stays out of every subnet's measured latency."""
    arch = _tiny_arch()
    cfg = arch.make_smoke()
    server = serve.build_server(arch, cfg, max_batch=2)
    seen, real = [], server.executable

    def spy(spec, bucket=None):
        fn = real(spec, bucket)

        def call(p, x):
            seen.append(x)
            return fn(p, x)
        return call

    monkeypatch.setattr(server, "executable", spy)
    x = np.zeros((2, cfg.img_res, cfg.img_res, 3), np.float32)
    server.measure(SubnetSpec(), x, iters=2)
    home = jax.tree_util.tree_leaves(server.params)[0].devices()
    assert len(seen) == 3
    assert all(isinstance(a, jax.Array) and a.devices() == home
               for a in seen)
