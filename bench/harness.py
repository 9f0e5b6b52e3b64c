"""Run one benchmark cell once and print its result line.

Everything is found by name from ``BENCHMARK.json`` at the checkout's
root: the workload names its configuration (``configs[].file``), its
traffic mix (``bench/traffic/<traffic>.json``, whose ``kind`` names the
generator ``bench/traffic/<kind>.py``) and the chips it needs; the
configuration names its model family (``bench/families/<family>.py``,
through which alone the harness reads what is particular to a model:
see ``FAMILY_API``); each metric it reports is read by
``bench/metrics/<metric>.py``.  A new cell, mix, model family or metric
is new files and entries, and no edit here.

A run: check the device; build the servers through the serving code's
own entry point with the benchmark's seeded weights swapped in; measure
the latency table and warm the cell's executables (all of that is
``setup_s``); drive the window; wait for every reply; compare a seeded
sample of the served answers with the plain reference; print the
numbers compared beside their limits, and last the result line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import re
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import stats

ROOT = Path(__file__).resolve().parent.parent

POOL, WEIGHTS, SAMPLE = 3, 4, 5          # seed streams of a run
TRACE_SECONDS = 3.0                      # profiler window, mid-run
TRACER_CAP = 1 << 19                     # keeps every request's spans

# what a model family's file provides (``bench/families/vit.py`` says
# what each name is)
FAMILY_API = ("FULL", "model_of", "make_weights", "check_layout", "pool",
              "reference", "rel_err", "flops_per_request", "roofline_s",
              "spec")


class BenchError(SystemExit):
    """A run that cannot give a result: exit 1, no result line."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# --- finding a cell by name --------------------------------------------------

def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing {path}")


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path}")
    mod_name = "bench_" + re.sub(r"\W", "_", str(path.relative_to(path.parents[1])))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(root: Path, name: str):
    """The model family ``name`` (``bench/families/<name>.py``); refuses
    one that lacks a name of ``FAMILY_API``."""
    fam = load_module(root / "bench" / "families" / f"{name}.py")
    missing = [k for k in FAMILY_API if not hasattr(fam, k)]
    if missing:
        raise BenchError(f"model family {name!r} lacks {missing}")
    return fam


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    mix: dict
    gen: object
    family: object
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    @property
    def name(self) -> str:
        return self.workload["name"]

    def reader(self, metric: str) -> Callable:
        return load_module(self.root / "bench" / "metrics"
                           / f"{metric}.py").read


def find_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == wl["config"]), None)
    if cfg_entry is None:
        raise BenchError(f"workload {name!r}: no config {wl['config']!r}")
    config = load_json(root / cfg_entry["file"])
    if "family" not in config:
        raise BenchError(f"{cfg_entry['file']} names no model family")
    family = load_family(root, config["family"])
    mix = load_json(root / "bench" / "traffic" / f"{wl['traffic']}.json")
    gen = load_module(root / "bench" / "traffic" / f"{mix['kind']}.py")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
    return Cell(wl, config, mix, gen, family, e2e, per_layer, root)


# --- the device --------------------------------------------------------------

def load_peaks(root: Path = ROOT) -> dict:
    return load_json(root / "bench" / "peaks.json")


def check_device(devices, chips: int, peaks: dict):
    """The row of peaks.json for this device; refuses no TPU, too few
    chips, and a kind the table does not hold."""
    d0 = devices[0]
    if d0.platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {d0.platform}")
    if len(devices) < chips:
        raise BenchError(f"cell needs {chips} chips, JAX has {len(devices)}")
    if d0.device_kind not in peaks:
        raise BenchError(f"device kind {d0.device_kind!r} is not in "
                         f"bench/peaks.json")
    return peaks[d0.device_kind]


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts XLA executables built, and how many the cache served."""

    def __init__(self):
        import jax
        self.built = 0
        self.cache_hits = 0
        self.on = True
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _event(self, event, **_):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.built = self.cache_hits = 0


# --- what the metric readers see --------------------------------------------

@dataclasses.dataclass
class Run:
    """One run's records, handed to every metric reader."""
    cell: Cell
    window: "object"                 # loop.Window
    seconds: float
    chips: int
    peak: dict
    setup_s: float
    deadline_ms: Optional[np.ndarray]
    switches: int                    # governor switches in the window
    served: List[int]                # per replica, in the window
    batches: List[dict]              # traced runs: one per batch
    profile: Optional[dict] = None   # traced runs: bench.trace_reduce
    trace_window: Optional[tuple] = None   # (t0, t1) perf_counter

    @property
    def model(self) -> dict:
        return self.cell.config["model"]

    @property
    def family(self):
        return self.cell.family

    def flops(self, subnet: str) -> float:
        return self.family.flops_per_request(self.model, subnet)


def batches_of(tracer) -> List[dict]:
    """One record per dispatched batch, from the engine's request spans:
    host stacking and dispatch ms, device span, subnet, bucket, size."""
    seen = {}
    for tr in tracer.requests():
        sp = {s.name: s for s in tr.spans}
        dev = sp.get("device")
        if dev is None or "stack" not in sp:
            continue
        key = (tr.node, sp["stack"].t0)
        if key in seen:
            continue
        seen[key] = {"node": tr.node, "t_stack": sp["stack"].t0,
                     "stack_ms": sp["stack"].dur_ms,
                     "dispatch_ms": sp["dispatch"].dur_ms,
                     "t_dispatch": sp["dispatch"].t0,
                     "t_disp_ret": sp["dispatch"].t1,
                     "t_ready": dev.t1, "subnet": dev.attrs["subnet"],
                     "bucket": dev.attrs["bucket"], "n": dev.attrs["n"]}
    return sorted(seen.values(), key=lambda b: b["t_stack"])


# --- a run -------------------------------------------------------------------

def check_model(family, cfg, model: dict):
    """The configuration file has to state what the program runs."""
    theirs = json.loads(json.dumps(family.model_of(cfg)))
    if theirs != model:
        raise BenchError(f"config file model {model} is not what the "
                         f"program builds: {theirs}")


def spec_of(family, name: str):
    spec = family.spec(name)
    if spec.name() != name:
        raise BenchError(f"sub-network {name!r} reads back as {spec.name()}")
    return spec


def log_window(win):
    """Where a run's tail came from: the generator's lateness, the longest
    stretch with no reply, and the slowest requests (seconds into the
    window, ms from due to reply)."""
    if not len(win.due):
        return
    late = np.sort(win.sent - win.due)
    lat = np.where(np.isnan(win.reply), np.inf, win.reply - win.due)
    replies = np.sort(win.reply[~np.isnan(win.reply)])
    gap = np.diff(replies)
    worst = np.argsort(-lat)[:5]
    log(f"generator late p99 {late[int(0.99 * (len(late) - 1))] * 1e3:.3f} "
        f"ms, max {late[-1] * 1e3:.3f} ms; longest stretch with no reply "
        f"{(gap.max() if len(gap) else 0) * 1e3:.3f} ms at "
        f"{(replies[int(np.argmax(gap))] - win.t0 if len(gap) else 0):.3f} "
        f"s; slowest: " + ", ".join(
            f"{win.due[k] - win.t0:.3f} s: {lat[k] * 1e3:.1f} ms"
            for k in worst))


class GcLog:
    """Python's garbage collections while it is open: each full (oldest
    generation) one, and how long collections held the process."""

    def __init__(self):
        self.full: List[tuple] = []      # (start, seconds)
        self.total_s = 0.0
        self.n = 0
        self._t = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        dt = time.perf_counter() - self._t
        self.n += 1
        self.total_s += dt
        if info["generation"] == 2:
            self.full.append((self._t, dt))

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def report(self, t0: float):
        longest = max(self.full, key=lambda f: f[1], default=None)
        log(f"garbage collections in the window: {self.n}, "
            f"{self.total_s * 1e3:.3f} ms in all; {len(self.full)} full"
            + (f", longest {longest[1] * 1e3:.3f} ms at "
               f"{longest[0] - t0:.3f} s" if longest else ""))


class TargetClock:
    """The latency target the mix sets, at the time the governor asks."""

    def __init__(self, cell: Cell):
        self.gen, self.mix = cell.gen, cell.mix
        self.seconds, self.t0 = 1.0, None

    def start(self, t0: float, seconds: float, mix: dict):
        self.t0, self.seconds, self.mix = t0, seconds, mix

    def now_ms(self) -> float:
        t = 0.0 if self.t0 is None else time.perf_counter() - self.t0
        return self.gen.target_ms(self.mix, t, self.seconds)


class Profiler(threading.Thread):
    """A profiler window of ``TRACE_SECONDS`` in the middle of the run."""

    def __init__(self, t0: float, seconds: float, out_dir: Path):
        super().__init__(daemon=True)
        self.start_at = t0 + max(0.0, seconds / 2 - TRACE_SECONDS / 2)
        self.length = min(TRACE_SECONDS, seconds)
        self.out_dir = out_dir
        self.window = None
        self.error = None

    def run(self):
        import jax
        try:
            from bench.loop import sleep_until
            sleep_until(self.start_at)
            opts = jax.profiler.ProfileOptions()
            # no host events: the host's layout transform of every input
            # batch records millions of them and throttles the served path
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(str(self.out_dir),
                                     profiler_options=opts)
            try:
                a = time.perf_counter()
                sleep_until(a + self.length)
                b = time.perf_counter()
            finally:
                jax.profiler.stop_trace()
            self.window = (a, b)
        except Exception as e:  # noqa: BLE001 - reported by the caller
            self.error = e


class Session:
    """A cell's servers, set up once, and the windows driven through them.

    ``plant`` (tests only) may alter each server before it warms."""

    def __init__(self, cell: Cell, seed: int, trace: bool, t_start: float,
                 plant: Optional[Callable] = None):
        import jax
        from repro.launch.cache import use_compile_cache
        from repro.configs import get_arch
        from repro.launch.serve import build_server, measure_lut
        from repro.obs import Tracer
        from repro.runtime import Constraints, JointGovernor

        self.cell, self.trace, self.t_start = cell, trace, t_start
        cache = use_compile_cache()
        devices = jax.devices()
        if devices[0].platform == "tpu":
            # every program, small ones too, goes to the persistent cache
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
        config = cell.config
        self.chips = chips = cell.workload["chips"]
        self.peak = check_device(devices, chips, load_peaks(cell.root))
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind, "count": len(devices)}
        log(f"device: {self.device}; compile cache: {cache}")
        arch = get_arch(config["arch"])
        cfg = arch.make_config()
        self.model = config["model"]
        check_model(cell.family, cfg, self.model)
        self.used = devices[:chips]
        self.counter = CompileCounter()
        self.servers = []
        for dev in self.used:
            srv = build_server(arch, cfg, max_batch=config["max_batch"],
                               device=dev)
            if plant is not None:
                plant(srv)
            self.servers.append(srv)
        self.reseed(seed)
        specs = [spec_of(cell.family, n) for n in config["subnets"]]
        for srv in self.servers:
            srv.warm(specs, example_input=self.pool[0])
        # timed once the cell's programs are built and have run, so the
        # table the governor plans on is not taken amid set-up
        lut = measure_lut(self.servers[0], specs,
                          self.pool[:config["max_batch"]])
        lat = {p.subnet.name(): round(p.latency_ms, 4) for p in lut.points
               if p.hw_state.freq == 1.0}
        log(f"latency table, batch {config['max_batch']} (ms): {lat}")

        self.tracer = Tracer(cap=TRACER_CAP) if trace else None
        self.clock = TargetClock(cell)
        self.cluster = None
        if chips == 1:
            srv = self.servers[0]
            srv.governor = JointGovernor(lut)
            srv.tracer = self.tracer
            srv.start(constraints_fn=lambda: Constraints(
                target_latency_ms=self.clock.now_ms(), chips_available=1))
            self.submit = lambda x: (0, srv.submit(x))
        else:
            from repro.cluster import P2C, Cluster, ClusterNode
            from repro.runtime import GlobalConstraints
            nodes = [ClusterNode(name=f"node{i}", g_fn=lambda t:
                                 GlobalConstraints(total_chips=1))
                     for i in range(chips)]
            self.cluster = Cluster(nodes, router=P2C, tracer=self.tracer)
            self.cluster.register(
                "cell", lut, target_latency_ms=self.clock.now_ms(),
                make_server=lambda node: self.servers[nodes.index(node)])
            self.cluster.start()
            port = self.cluster.port("cell")
            picks = self.cluster.router.decisions
            lane = {node.name: i for i, node in enumerate(nodes)}

            def submit(x):
                # the lane is the replica the router just picked: this
                # thread alone submits, so the newest pick is this one's
                fut = port.submit(x)
                return lane[picks[-1][2]] if picks else 0, fut
            self.submit = submit

    def reseed(self, seed: int):
        """The seed's weights (swapped into every server) and input pool."""
        import jax
        fam = self.cell.family
        self.w = fam.make_weights(self.model, stats.seed32(seed, WEIGHTS),
                                  self.used[0])
        for dev, srv in zip(self.used, self.servers):
            fam.check_layout(self.w, srv.params)
            srv.params = self.w if dev == self.used[0] \
                else jax.device_put(self.w, dev)
        rng = np.random.default_rng(stats.seed32(seed, POOL))
        self.pool = fam.pool(self.model, self.cell.mix["pool"], rng)

    def drive(self, seconds: float, seed: int, mix: Optional[dict] = None
              ) -> Run:
        """One measured window; ``mix`` overrides the cell's (sweeps)."""
        cell = self.cell
        mix = cell.mix if mix is None else mix
        servers = self.servers
        switches0 = sum(len(s.switch_log) + s.switch_log_dropped
                        for s in servers)
        served0 = [s.served for s in servers]
        prof = {}

        def on_start(t0: float):
            self.clock.start(t0, seconds, mix)
            prof["setup_s"] = t0 - self.t_start
            log(f"before the window: {self.counter.built} programs "
                f"compiled, {self.counter.cache_hits} read from the "
                f"compile cache")
            self.counter.reset()
            if self.trace:
                out = cell.root / "bench" / "out" / "trace"
                shutil.rmtree(out, ignore_errors=True)
                prof["thread"] = Profiler(t0, seconds, out)
                prof["thread"].start()
                prof["dir"] = out

        log(f"window: {seconds} s of {cell.workload['traffic']} "
            f"({mix['kind']}), seed {seed}")
        # set-up ends with a full collection, so none owed to set-up's
        # garbage falls due in the window
        gc.collect()
        with GcLog() as gcl:
            win = cell.gen.drive(mix, self.submit, self.pool, seconds, seed,
                                 on_start=on_start, lanes=self.chips)
        self.compiles = self.counter.built
        log(f"compiles in the window: {self.compiles}")
        log_window(win)
        gcl.report(win.t0)
        deadline = None
        if hasattr(cell.gen, "deadline_ms"):
            deadline = np.array([cell.gen.deadline_ms(mix, d - win.t0,
                                                      seconds)
                                 for d in win.due])
        run = Run(cell=cell, window=win, seconds=seconds, chips=self.chips,
                  peak=self.peak, setup_s=prof["setup_s"],
                  deadline_ms=deadline,
                  switches=sum(len(s.switch_log) + s.switch_log_dropped
                               for s in servers) - switches0,
                  served=[s.served - b for s, b in zip(servers, served0)],
                  batches=(batches_of(self.tracer)
                           if self.tracer is not None else []))
        if self.trace:
            th = prof["thread"]
            th.join()
            if th.error is not None or th.window is None:
                raise BenchError(f"profiler window failed: {th.error!r}")
            from bench import trace_reduce
            run.trace_window = th.window
            run.profile = trace_reduce.reduce_dir(prof["dir"], self.used,
                                                  run)
        return run

    def close(self) -> dict:
        """Stop serving, read the memory peak, free the program's state;
        returns the result's ``device`` object."""
        self.counter.on = False
        if self.cluster is not None:
            self.cluster.stop()
        else:
            self.servers[0].stop()
        self.device["memory_peak_bytes"] = memory_peak_bytes(self.used)
        self.servers = self.cluster = self.submit = self.tracer = None
        gc.collect()
        return self.device


def sample_answers(cell: Cell, win, seed: int) -> Dict[str, List[int]]:
    """A seeded sample of the answered requests, ``check_sample`` in all,
    each sub-network served getting its share: {sub-network: [request]}."""
    known = set(cell.config["subnets"])
    groups: Dict[str, List[int]] = {}
    for k in range(len(win.out)):
        if win.error[k] is None and win.subnet[k] in known:
            groups.setdefault(win.subnet[k], []).append(k)
    rng = np.random.default_rng(stats.seed32(seed, SAMPLE))
    per = max(1, cell.mix["check_sample"] // max(1, len(groups)))
    return {name: sorted(int(k) for k in rng.choice(
        ks, min(per, len(ks)), replace=False))
        for name, ks in sorted(groups.items())}


def check_answers(cell: Cell, run: Run, w, pool, seed: int) -> Dict:
    """Compare a seeded sample of served answers with the family's plain
    reference; ``rel_err`` reduces each answer, of whatever shape, to one
    number."""
    fam = cell.family
    win, config = run.window, cell.config
    known = set(config["subnets"])
    unknown = sum(1 for k in range(len(win.out)) if win.error[k] is None
                  and win.subnet[k] not in known)
    sample = sample_answers(cell, win, seed)
    worst, worst_at = 0.0, None
    for name, ks in sample.items():
        ref = fam.reference(w, pool[win.idx[ks]], config["model"], name)
        errs = fam.rel_err([win.out[k] for k in ks], ref)
        j = int(np.argmax(errs))
        if errs[j] > worst:
            worst, worst_at = float(errs[j]), (name, ks[j])
    log(f"checked {sum(map(len, sample.values()))} answers over "
        f"{len(sample)} sub-networks; worst {worst:.6g} at {worst_at}")
    return {
        "max_rel_err": {"value": worst,
                        "limit": config["correct"]["max_rel_err"]},
        "failed_requests": {"value": int(np.count_nonzero(win.failed)),
                            "limit": 0},
        "unknown_subnet": {"value": unknown, "limit": 0},
    }


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def run_cell(args, t_start: float, root: Path = ROOT,
             plant: Optional[Callable] = None) -> dict:
    """One run of one cell; returns the result object (last line)."""
    cell = find_cell(args.workload, root)
    session = Session(cell, args.seed, bool(args.trace), t_start, plant)
    run = session.drive(args.seconds, args.seed)
    device = session.close()
    if args.trace:
        device["busy_s"] = run.profile["busy_s"]
        device["window_s"] = run.profile["window_s"]
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = cell.reader(m["name"])(run)
        if value is None:
            if not args.trace:
                raise BenchError(f"end-to-end metric {m['name']} read "
                                 f"nothing")
            log(f"{m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    checks = check_answers(cell, run, session.w, session.pool, args.seed)
    win = run.window
    result = {"correct": passed(checks), "attempted": int(len(win.due)),
              "failed": int(np.count_nonzero(win.failed)),
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = run.profile["breakdown"]
    result["checks"] = checks
    return result


def main(argv=None, t_start: Optional[float] = None):
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args, t_start)
    print(json.dumps(result, allow_nan=False), flush=True)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
