"""The readers of the engine's batch spans: exact on a trace made by hand
whose idle split is known, sound on the trace recorded on a TPU v5e
(``fixtures/``) with batch spans built from its batches, and silent (None,
no exception) where the program records no batch spans."""
import gzip
import json
import types
from pathlib import Path

import numpy as np
import pytest

from bench import harness, readers, spans, trace_reduce
from repro.obs import Tracer
from repro.obs import trace as obs

BENCH = Path(__file__).resolve().parent
FIXTURE = BENCH / "fixtures" / "trace_v5e.json.gz"
METRICS = ["device.idle_host_pct.open", "device.idle_host_pct.offline",
           "device.idle_await_pct.open", "device.idle_await_pct.offline",
           "engine.queue_ms_p99.open", "engine.host_cpu_pct.open",
           "engine.host_cpu_pct.offline"]
MS = 1_000_000        # ns
OFFSET = 1.0          # device clock = perf_counter + 1 s


def read(metric, run):
    return harness.load_module(BENCH / "metrics" / f"{metric}.py").read(run)


def perf(ms):
    return ms * 1e-3 - OFFSET


def record(tracer, node, submit_ms, take_ms, collect, disp_ret, ready_dev,
           cpu_ms):
    s = tuple(perf(t) for t in submit_ms)
    k = tuple(perf(t) for t in take_ms)
    tracer.batch(k[0], perf(ready_dev + 1), node=node, bucket=2,
                 subnet="w1-f1-h1-d1", n=len(s), t_take_first=k[0],
                 t_collect=perf(collect), t_dispatch=perf(collect + 0.5),
                 t_disp_ret=perf(disp_ret), t_ready_dev=perf(ready_dev),
                 t_ready=perf(ready_dev + 0.5), t_done=perf(ready_dev + 1),
                 submit=s, take=k, cpu_stack_s=cpu_ms * 1e-3)


def hand_run(root: Path, chips: int):
    """Window [10, 30] ms on the device.  Chip 0: busy [10, 14] (an op
    straddles the start), [20, 23], [27, 30]: idle [14, 20] and [23, 27].
    Batch A: queue [8, 9], stack + dispatch [12, 15], awaiting [15, 19].
    Batch B: queues [18, 24] and [23.5, 24.5], stack + dispatch [25, 26],
    awaiting [26, 26.5].  Host-held less awaiting: [8, 9], [12, 15],
    [19, 24.5], [25, 26]; so of the 10 ms idle, host 4.5 ms ([14, 15],
    [19, 20], [23, 24.5], [25, 26]), await 4.5 ms ([15, 19], [26, 26.5]),
    no work 1 ms ([24.5, 25], [26.5, 27]).  Chip 1 (two chips): busy but
    for [23, 27], and its node has no batch there: 4 ms of no work."""
    devices = {"0": {"modules": [], "ops": [
        ["%early", 8 * MS, 3 * MS], ["%a", 10 * MS, 4 * MS],
        ["%b", 20 * MS, 3 * MS], ["%c", 27 * MS, 3 * MS]]}}
    tracer = Tracer()
    node = None if chips == 1 else "node0"
    record(tracer, node, [8], [9], 12, 15, 19, 1.5)
    record(tracer, node, [18, 23.5], [24, 24.5], 25, 26, 26.5, 0.5)
    if chips == 2:
        devices["1"] = {"modules": [], "ops": [
            ["%x", 10 * MS, 13 * MS], ["%y", 27 * MS, 3 * MS]]}
        record(tracer, "node1", [10], [11], 11, 12, 22, 1.0)
    path = root.joinpath(*spans.CONDENSED)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps({"devices": devices}))
    busy = [0.010] + ([0.016] if chips == 2 else [])
    return types.SimpleNamespace(
        profile={"offsets_s": [OFFSET] * chips, "window_s": 0.020,
                 "busy_s": sum(busy) / chips},
        trace_window=(perf(10), perf(30)), chips=chips,
        cell=types.SimpleNamespace(root=root),
        window=types.SimpleNamespace(t0=perf(0), t1=perf(40)))


# chip 0: host 4.5, await 4.5, no work 1 of 20 ms; chip 1: no work 4
HAND = {1: {"idle": 50.0, "host": 22.5, "await": 22.5, "no_work": 5.0},
        2: {"idle": 35.0, "host": 11.25, "await": 11.25, "no_work": 12.5}}


@pytest.mark.parametrize("chips", [1, 2])
def test_hand_made_split_is_exact(tmp_path, chips):
    run = hand_run(tmp_path, chips)
    split = spans.idle_split(run)
    assert split == pytest.approx(HAND[chips], abs=1e-9)
    assert split["idle"] == pytest.approx(readers.idle_pct(run), abs=1e-9)


@pytest.mark.parametrize("metric, chips, want", [
    ("device.idle_host_pct.open", 1, 22.5),
    ("device.idle_host_pct.offline", 2, 11.25),
    ("device.idle_await_pct.open", 1, 22.5),
    ("device.idle_await_pct.offline", 2, 11.25),
    # waits 1, 6 and 1 ms on chip 0 (and 1 ms on chip 1)
    ("engine.queue_ms_p99.open", 1, 6.0),
    # CPU 1.5 + 0.5 ms of 3 + 1 ms (and 1 of 1 ms on chip 1)
    ("engine.host_cpu_pct.open", 1, 50.0),
    ("engine.host_cpu_pct.offline", 2, 60.0),
])
def test_metric_reads_the_hand_made_run(tmp_path, metric, chips, want):
    assert read(metric, hand_run(tmp_path, chips)) == pytest.approx(
        want, abs=1e-9)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The v5e trace, its reduction, and batch spans made from its
    batches with seeded queue waits (each request submitted 0-4 ms
    before it was taken, taken 0-2 ms before stacking began)."""
    with gzip.open(FIXTURE, "rt") as f:
        tr = json.load(f)
    batches = tr["run"]["batches"]
    window = tuple(tr["run"]["window_perf"])
    profile = trace_reduce.reduce(tr, 1, batches, window)
    root = tmp_path_factory.mktemp("checkout")
    path = root.joinpath(*spans.CONDENSED)
    path.parent.mkdir(parents=True)
    path.write_text(json.dumps(tr))
    rng = np.random.default_rng(2**31 + 11)
    tracer = Tracer()
    for b in batches:
        take = np.sort(b["t_stack"] - rng.uniform(0, 2e-3, b["n"]))
        submit = take - rng.uniform(0, 4e-3, b["n"])
        tracer.batch(float(take[0]), b["t_ready"], node=b["node"],
                     bucket=b["bucket"], subnet=b["subnet"], n=b["n"],
                     t_collect=b["t_stack"], t_dispatch=b["t_dispatch"],
                     t_disp_ret=b["t_disp_ret"], t_ready_dev=b["t_ready"],
                     t_ready=b["t_ready"], t_done=b["t_ready"],
                     submit=tuple(submit), take=tuple(take),
                     cpu_stack_s=0.8 * (b["t_disp_ret"] - b["t_stack"]))
    return types.SimpleNamespace(
        profile=profile, trace_window=window, chips=1,
        cell=types.SimpleNamespace(root=root),
        window=types.SimpleNamespace(t0=window[0], t1=window[1]))


def test_recorded_v5e_split_sums_to_idle(recorded):
    logged = []
    split = spans.idle_split(recorded, logged.append)
    idle = readers.idle_pct(recorded)
    assert split["host"] > 0 and split["await"] > 0
    assert split["no_work"] >= 0
    assert split["host"] + split["await"] + split["no_work"] == \
        pytest.approx(idle, abs=0.1)
    assert split["idle"] == pytest.approx(idle, abs=1e-6)
    assert len(logged) == 2 and "device.idle_pct" in logged[-1]
    assert spans.host_cpu_pct(recorded) == pytest.approx(80.0)
    assert 0 < spans.queue_ms_p99(recorded) <= 6.0


@pytest.mark.parametrize("seed", range(4))
def test_interval_arithmetic_matches_a_grid(seed):
    rng = np.random.default_rng(seed)

    def ivs():
        starts = rng.integers(0, 90, 8)
        return trace_reduce.union([(int(s), int(s + rng.integers(1, 15)))
                                   for s in starts])
    a, b = ivs(), ivs()
    grid = np.arange(0, 120) + 0.5

    def mask(iv):
        return np.any([(grid > s) & (grid < e) for s, e in iv], axis=0) \
            if iv else np.zeros_like(grid, bool)
    assert spans.overlap(a, b) == np.count_nonzero(mask(a) & mask(b))
    assert spans.measure(spans.subtract(a, b)) == np.count_nonzero(
        mask(a) & ~mask(b))
    assert spans.measure(spans.gaps(a, 0, 120)) == np.count_nonzero(
        ~mask(a))


class NoBatchSpans:
    """A tracer of a program that records no batch spans."""


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("program", ["no handle", "no batch spans", "empty"])
def test_a_program_without_batch_spans_reads_nothing(tmp_path, monkeypatch,
                                                     metric, program):
    run = hand_run(tmp_path, 2 if metric.endswith("offline") else 1)
    if program == "no handle":
        monkeypatch.delattr(obs, "current_tracer")
    elif program == "no batch spans":
        monkeypatch.setattr(obs, "current_tracer", NoBatchSpans)
    else:
        Tracer()
    assert read(metric, run) is None
