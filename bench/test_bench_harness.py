"""The harness finds a cell by name from files alone, its model family
among them, refuses a device it has no peaks for, runs a cell end to end
on the CPU at a small size, open loop and closed, and reads ``correct``
false when the served path is broken underneath, the lower-precision
control serves in its place, or the family's reference is off."""
import argparse
import dataclasses
import functools
import json
import shutil
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import numpy as np
import repro.configs
from bench import harness, loop
from bench.families import vit

BENCH = Path(__file__).resolve().parent

SMOKE_OFA = {
    "name": "ofa-smoke", "arch": "dynamic-ofa-supernet", "family": "vit",
    "model": {"img_res": 32, "patch": 8, "n_layers": 6, "d_model": 64,
              "n_heads": 4, "d_ff": 256, "n_classes": 10,
              "distill_token": False, "exit_layers": [1, 3, 5],
              "param_dtype": "float32", "compute_dtype": "float32"},
    "subnets": ["w1-f1-h1-d1", "w0.5-f0.25-h0.5-d0.333333"],
    "max_batch": 8, "reduced": [],
    # float32 both sides at this size: the tanh GELU reads ~3e-4
    "correct": {"max_rel_err": 1e-2}}

MIX = {"kind": "open_poisson", "rate_rps": 300, "base_target_ms": 2.0,
       "target_mults": [1.0, 0.1, 2.0], "phases": 6, "service_frac": 0.5,
       "pool": 16, "grace_s": 30, "check_sample": 24}

CLOSED = {"kind": "closed", "outstanding": 32, "target_ms": 10000.0,
          "pool": 16, "grace_s": 30, "check_sample": 24}

# the same model read through other family files: a copy of ``vit.py``,
# and one whose reference moves every answer by a tenth of its largest
# logit
SHIFT = """
_vit_reference = reference


def reference(w, xs, model, subnet, **kw):
    y = _vit_reference(w, xs, model, subnet, **kw)
    return y + 0.1 * np.max(np.abs(y), axis=-1, keepdims=True)
"""
# tiny cell: (config, traffic, family file, the chip cells whose metrics
# it reports)
TINY = {"tiny.moving": ("ofa-smoke", "tiny_moving", "vit", "ofa-s.moving"),
        "tiny.copy": ("ofa-smoke-copy", "tiny_moving", "vit_copy",
                      "ofa-s.moving"),
        "tiny.shifted": ("ofa-smoke-shifted", "tiny_moving", "vit_shifted",
                         "ofa-s.moving"),
        "tiny.closed": ("ofa-smoke", "tiny_closed", "vit",
                        "deit-b.replicas4")}


def write(path: Path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding new cells: their files and entries only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH / "metrics", root / "bench" / "metrics")
    (root / "bench" / "traffic").mkdir()
    for kind in ("open_poisson", "closed"):
        shutil.copy(BENCH / "traffic" / f"{kind}.py",
                    root / "bench" / "traffic")
    write(root / "bench" / "traffic" / "tiny_moving.json", MIX)
    write(root / "bench" / "traffic" / "tiny_closed.json", CLOSED)
    families = root / "bench" / "families"
    shutil.copytree(BENCH / "families", families,
                    ignore=shutil.ignore_patterns("__pycache__"))
    src = (families / "vit.py").read_text()
    (families / "vit_copy.py").write_text(src)
    (families / "vit_shifted.py").write_text(src + SHIFT)
    configs = {}
    for config, _, family, _ in TINY.values():
        configs[config] = dict(SMOKE_OFA, name=config, family=family)
        write(root / "bench" / "configs" / f"{config}.json", configs[config])
    write(root / "bench" / "peaks.json", {"cpu": {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
        "source": "a stand-in row for the CPU"}})
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": c, "source": "test",
                         "file": f"bench/configs/{c}.json",
                         "reduced": [], "why": "test"} for c in configs]
    bench["workloads"] = [{"name": name, "config": config,
                           "traffic": traffic, "chips": 1, "why": "test"}
                          for name, (config, traffic, _, _) in TINY.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [name for name, (*_, chip_cell) in TINY.items()
                              if chip_cell in m["workloads"]]
    write(root / "BENCHMARK.json", bench)
    return root


@pytest.fixture
def on_cpu(monkeypatch):
    """Runs a cell on the CPU: the device check passes and the
    configuration builds at the smoke size the config file states."""
    monkeypatch.setattr(harness, "check_device",
                        lambda devices, chips, peaks: peaks["cpu"])
    get_arch = repro.configs.get_arch
    monkeypatch.setattr(repro.configs, "get_arch", lambda name: dataclasses
                        .replace(get_arch(name),
                                 make_config=get_arch(name).make_smoke))


def args(seconds=1.0, seed=2**31 + 5, workload="tiny.moving"):
    return argparse.Namespace(workload=workload, seed=seed,
                              seconds=seconds, trace=0)


def test_cell_is_found_from_its_files(root):
    cell = harness.find_cell("tiny.moving", root)
    assert cell.config["subnets"] == SMOKE_OFA["subnets"]
    assert cell.mix["rate_rps"] == 300
    assert cell.gen.target_ms(cell.mix, 0.0, 6.0) == 2.0
    assert [m["name"] for m in cell.end_to_end] == [
        "goodput_rps", "top1_surrogate_pct", "setup_s"]
    assert "engine.host_ms_per_batch.open" in [m["name"]
                                               for m in cell.per_layer]
    with pytest.raises(harness.BenchError):
        harness.find_cell("no.such.cell", root)


def test_refuses_a_device_kind_without_peaks():
    peaks = harness.load_peaks()
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v0")
    with pytest.raises(harness.BenchError, match="not in bench/peaks.json"):
        harness.check_device([unknown], 1, peaks)
    known = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert harness.check_device([known], 1, peaks) is peaks["TPU v5 lite"]
    with pytest.raises(harness.BenchError, match="4 chips"):
        harness.check_device([known], 4, peaks)
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.check_device(jax.devices(), 1, {"cpu": {}})


def test_refuses_a_config_the_program_does_not_run(root):
    cell = harness.find_cell("tiny.moving", root)
    from repro.configs import get_arch
    cfg = get_arch("dynamic-ofa-supernet").make_config()
    with pytest.raises(harness.BenchError, match="not what the program"):
        harness.check_model(cell.family, cfg, cell.config["model"])


def test_runs_end_to_end_on_cpu(root, on_cpu):
    result = harness.run_cell(args(), time.perf_counter(), root)
    assert list(result)[-1] == "checks"
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 300
    assert set(result["metrics"]) == {"goodput_rps",
                                      "top1_surrogate_pct", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert result["checks"]["max_rel_err"]["value"] < 1e-2


def _alter_answer(fn):
    def f(p, x, E):
        y = fn(p, x, E)
        return y.at[:, 0].add(0.1 * jnp.max(jnp.abs(y)))
    return f


def _swap_rows(fn):
    return lambda p, x, E: jnp.roll(fn(p, x, E), 1, axis=0)


def _half_batch(fn):
    def f(p, x, E):
        return fn(p, x.at[x.shape[0] // 2:].set(0.0), E)
    return f


def _other_subnet(fn):
    def f(p, x, E):
        return fn(p, x, dict(E, a_ff=max(1, E["a_ff"] // 2)))
    return f


@pytest.mark.parametrize("fault", [_alter_answer, _swap_rows, _half_batch,
                                   _other_subnet])
def test_broken_path_reads_incorrect(root, on_cpu, fault):
    def plant(server):
        server.apply_fn = fault(server.apply_fn)
    result = harness.run_cell(args(seconds=0.5), time.perf_counter(), root,
                              plant=plant)
    assert not result["correct"], result["checks"]


def test_lower_precision_control_reads_incorrect(root, on_cpu):
    """The reference at float8 matrix products, served in the program's
    place, fails the limit through the harness's own comparison."""
    model = SMOKE_OFA["model"]

    def plant(server):
        @functools.lru_cache(maxsize=None)
        def executable(spec, bucket=None):
            return jax.jit(functools.partial(
                vit.forward, model=model, name=spec.name(), fp8=True))
        server.executable = executable
    result = harness.run_cell(args(seconds=0.5), time.perf_counter(), root,
                              plant=plant)
    assert result["failed"] == 0
    err = result["checks"]["max_rel_err"]
    assert err["value"] > 3 * err["limit"], err
    assert not result["correct"]


def test_runs_a_one_chip_closed_loop_on_cpu(root, on_cpu):
    result = harness.run_cell(args(workload="tiny.closed"),
                              time.perf_counter(), root)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 32
    assert set(result["metrics"]) == {"images_per_s",
                                      "top1_surrogate_pct", "setup_s"}
    assert result["metrics"]["images_per_s"]["value"] > 0


def test_the_family_the_config_names_is_the_one_read(root, on_cpu):
    """The tiny cell through a copy of ``vit.py`` filed as ``vit_copy``
    reads the same FLOPs, surrogate, reference answers and ``correct`` as
    through ``vit``."""
    cells = [harness.find_cell(n, root) for n in ("tiny.moving", "tiny.copy")]
    assert [Path(c.family.__file__).name for c in cells] == [
        "vit.py", "vit_copy.py"]
    assert cells[0].family is not cells[1].family
    subnets = SMOKE_OFA["subnets"]
    n = 2 * len(subnets)
    win = loop.Window(t0=0.0, t1=1.0, due=np.zeros(n), sent=np.zeros(n),
                      reply=np.full(n, 0.5), idx=np.zeros(n, np.int64),
                      out=[None] * n, subnet=subnets * 2, error=[None] * n)
    runs = [harness.Run(cell=c, window=win, seconds=1.0, chips=1, peak={},
                        setup_s=1.0, deadline_ms=None, switches=0,
                        served=[], batches=[]) for c in cells]
    assert [runs[0].flops(s) for s in subnets] == [
        runs[1].flops(s) for s in subnets]
    assert [c.reader("top1_surrogate_pct")(r) for c, r in zip(
        cells, runs)] == [cells[0].reader("top1_surrogate_pct")(runs[0])] * 2
    model = SMOKE_OFA["model"]
    w = cells[0].family.make_weights(model, 7, jax.devices()[0])
    x = cells[0].family.pool(model, 3, np.random.default_rng(1))
    assert np.array_equal(x, cells[1].family.pool(
        model, 3, np.random.default_rng(1)))
    for s in subnets:
        assert np.array_equal(cells[0].family.reference(w, x, model, s),
                              cells[1].family.reference(w, x, model, s))
    results = [harness.run_cell(args(seconds=0.5, workload=c.name),
                                time.perf_counter(), root) for c in cells]
    assert [r["correct"] for r in results] == [True, True], [
        r["checks"] for r in results]


def test_a_family_whose_reference_is_off_reads_incorrect(root, on_cpu):
    cell = harness.find_cell("tiny.shifted", root)
    assert Path(cell.family.__file__).name == "vit_shifted.py"
    result = harness.run_cell(args(seconds=0.5, workload="tiny.shifted"),
                              time.perf_counter(), root)
    assert result["failed"] == 0
    err = result["checks"]["max_rel_err"]
    assert err["value"] > err["limit"], err
    assert not result["correct"]
