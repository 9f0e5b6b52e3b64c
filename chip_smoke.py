"""Bring-up check: the Dynamic-OFA serving path, end to end, on a TPU.

    python chip_smoke.py             # one chip: governed serving
    python chip_smoke.py --chips 4   # four one-chip replicas behind p2c

One process drives every chip it uses.  The model is
``dynamic-ofa-supernet`` at its ``make_config`` widths (224 px, d_model
384, 12 layers, 197 tokens) with seeded random weights, served through
the normal entry points (``repro.launch.serve.build_server``,
``DynamicServer``, ``JointGovernor``; ``Cluster`` for the replicas).

The run fails -- non-zero exit, no result line -- when JAX finds no TPU,
when any request fails, when serving has to compile, when the governor
never switches subnets, or when an output disagrees with its reference.
Otherwise the last line is ``{"ok": true, "device": {...}}``.  Latencies
are host-clock numbers, printed for information only.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.cluster import P2C, Cluster, ClusterNode  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.core.elastic import spec_to_static  # noqa: E402
from repro.core.types import SubnetSpec  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.serve import (build_server, device_info,  # noqa: E402
                                measure_lut)
from repro.models.vit import vit_apply  # noqa: E402
from repro.obs import quantile  # noqa: E402
from repro.runtime import (Constraints, GlobalConstraints,  # noqa: E402
                           JointGovernor)
from repro.runtime import hwmodel as hm  # noqa: E402

ARCH = "dynamic-ofa-supernet"
MAX_BATCH = 8
# largest error / largest |reference| on a TPU v5e at seeds 0 and 1: sound
# answers (bf16 vs another bucket, bf16 vs float32) read 7e-3 to 1.5e-2;
# wrong ones (the next request's row, one ffn step down) read 0.70 to 0.87.
# The run checks both sides of this line.
TOL = 5e-2
# request waves: every bucket of the ladder (1, 2, 4, 8) gets used, and
# the latency target flips between waves so the governor must switch
WAVES = (1, 3, 8, 8, 2, 5, 8, 8, 4, 8, 8, 1)


class Fail(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAIL: {msg}")


class CompileCounter:
    """Counts XLA executables built, and how many came from the cache."""

    def __init__(self):
        self.built = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.built = self.cache_hits = 0


def rel_err(y, ref) -> float:
    """Largest absolute error as a fraction of the largest reference value."""
    y = np.asarray(y, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(y - ref)) / max(np.max(np.abs(ref)), 1e-6))


def images(cfg, n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).normal(
        size=(n, cfg.img_res, cfg.img_res, 3)).astype(np.float32)


def check_payloads(outs):
    bad = [o for o in outs
           if o.get("cancelled") or o.get("failed") or o.get("y") is None]
    if bad:
        raise Fail(f"{len(bad)}/{len(outs)} requests not served: "
                   f"{bad[0].get('error')}")


def serve_governed(arch, cfg, seed: int) -> None:
    """One governed server: measured LUT, warmed ladder, moving target."""
    counter = CompileCounter()
    server = build_server(arch, cfg, max_batch=MAX_BATCH)
    full, small = cfg.elastic.max_spec(), cfg.elastic.min_spec()
    mid = SubnetSpec(width_mult=0.75, ffn_mult=0.5, heads_mult=0.75,
                     depth_mult=0.5)
    specs = [full, mid, small]
    by_name = {s.name(): s for s in specs}
    x = images(cfg, MAX_BATCH, seed)

    counter.reset()
    t0 = time.perf_counter()
    lut = measure_lut(server, specs, x)
    server.warm(specs, example_input=x[0])
    warm_s = time.perf_counter() - t0
    print(f"warm-up: {warm_s:.3f} s, {counter.built} executables built "
          f"({counter.cache_hits} read from the compile cache) for "
          f"{len(specs)} subnets x buckets {server.buckets}")
    lat = {p.subnet.name(): p.latency_ms for p in lut.points
           if p.hw_state.freq == 1.0}
    hi, lo = lat[full.name()], lat[small.name()]
    print(f"measured batch-{MAX_BATCH} latency: full {hi:.3f} ms, "
          f"mid {lat[mid.name()]:.3f} ms, smallest {lo:.3f} ms")

    target = {"ms": hi}
    server.governor = JointGovernor(lut)
    server.start(constraints_fn=lambda: Constraints(
        target_latency_ms=target["ms"], chips_available=1), govern_every=1)
    xs = images(cfg, sum(WAVES), seed + 1)
    outs, i = [], 0
    try:
        for w, n in enumerate(WAVES):
            target["ms"] = hi if w % 2 == 0 else lo
            futs = [server.submit(xs[j]) for j in range(i, i + n)]
            outs += [f.get(timeout=120) for f in futs]
            i += n
    finally:
        server.stop()
    check_payloads(outs)
    if server.cold_compiles:
        raise Fail(f"{server.cold_compiles} cold compiles while serving")
    served = sorted({o["subnet"] for o in outs})
    if len(served) < 2:
        raise Fail(f"the governor served only {served}")
    lats = [o["latency_ms"] for o in outs]
    print(f"served {len(outs)} requests on {jax.devices()[0].device_kind}: "
          f"subnets {served}, {len(server.switch_log)} switches, cold "
          f"compiles {server.cold_compiles}; host-clock latency "
          f"p50={quantile(lats, 50):.3f} ms p99={quantile(lats, 99):.3f} ms")

    # each served row against the same subnet run directly on its input
    ys = np.stack([o["y"] for o in outs])
    rows = {n: [k for k, o in enumerate(outs) if o["subnet"] == n]
            for n in served}
    direct = {n: np.asarray(server.infer(xs, by_name[n])) for n in served}
    errs = {n: rel_err(ys[r], direct[n][r]) for n, r in rows.items()}
    # full-width rows against a float32 forward of the same params
    full_rows = rows.get(full.name(), [])
    if len(full_rows) < 2:
        raise Fail(f"{len(full_rows)} full-width rows; the checks need 2")
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")
    E = spec_to_static(full, server.dims)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(
            lambda p, a: vit_apply(p, a, cfg32, E=E)[0])(server.params, xs))
    err32 = rel_err(ys[full_rows], ref[full_rows])
    # what a wrong answer reads under the same measure: each full-width row
    # answered with the next full-width request's output, or by the
    # nearest smaller subnet (one ffn step down)
    near = SubnetSpec(ffn_mult=0.75)
    wrong = {
        "next request's row": rel_err(
            ys[full_rows], direct[full.name()][np.roll(full_rows, 1)]),
        f"subnet {near.name()}": rel_err(
            ys[full_rows], np.asarray(server.infer(xs, near))[full_rows]),
    }
    print("max error / max |ref|: served vs server.infer "
          + ", ".join(f"{n} {e:.3e}" for n, e in errs.items())
          + f"; full width vs float32 reference {err32:.3e} "
          f"({len(full_rows)} rows); tolerance {TOL:g}; wrong answers read "
          + ", ".join(f"{n} {e:.3e}" for n, e in wrong.items()))
    if max(errs.values()) > TOL or err32 > TOL:
        raise Fail("outputs disagree with their references")
    if min(wrong.values()) <= TOL:
        raise Fail("a wrong answer reads within the tolerance")


def serve_replicas(arch, cfg, devices, seed: int, n_requests: int = 64
                   ) -> None:
    """One-chip replicas behind the p2c router vs one node on one chip."""
    full = SubnetSpec()
    x = images(cfg, MAX_BATCH, seed)
    servers = [build_server(arch, cfg, max_batch=MAX_BATCH, device=d)
               for d in devices]
    homes = [{d for leaf in jax.tree_util.tree_leaves(s.params)
              for d in leaf.devices()} for s in servers]
    if homes != [{d} for d in devices]:
        raise Fail(f"replica params not one per device: {homes}")
    t0 = time.perf_counter()
    for s in servers:
        s.warm([full], example_input=x[0])
    print(f"warm-up: {time.perf_counter() - t0:.3f} s for {len(servers)} "
          f"replicas x buckets {servers[0].buckets}")
    lut = measure_lut(servers[0], [full], x,
                      hw_states=[hm.HwState(chips=1, freq=1.0)])
    ms = lut.points[0].latency_ms
    xs = images(cfg, n_requests, seed + 1)

    def run(n_nodes):
        nodes = [ClusterNode(name=f"node{i}",
                             g_fn=lambda t: GlobalConstraints(total_chips=1))
                 for i in range(n_nodes)]
        cluster = Cluster(nodes, router=P2C)
        cluster.register("api", lut, target_latency_ms=100 * ms,
                         make_server=lambda node: servers[nodes.index(node)])
        before = [s.served for s in servers[:n_nodes]]
        cluster.start()
        try:
            futs = [cluster.submit("api", xs[k]) for k in range(n_requests)]
            outs = [f.get(timeout=120) for f in futs]
        finally:
            cluster.stop()
        check_payloads(outs)
        served = [s.served - b for s, b in zip(servers, before)]
        return outs, served

    # the replicas first: the one-node rerun reuses servers[0], whose
    # arrival counts from the first run would skew a p2c pick
    four, served = run(len(servers))
    one, _ = run(1)
    for i, (d, n) in enumerate(zip(devices, served)):
        print(f"node{i}: {d.device_kind} id {d.id}, served {n}")
    if min(served) == 0:
        raise Fail(f"a replica served nothing: {served}")
    if {o["subnet"] for o in one + four} != {full.name()}:
        raise Fail("replicas served another subnet than the one-chip run")
    ys, ref = (np.stack([o["y"] for o in run_]) for run_ in (four, one))
    err = rel_err(ys, ref)
    # a wrong answer: each request answered with the next request's row
    wrong = rel_err(ys, np.roll(ref, 1, axis=0))
    print(f"{n_requests} requests through {len(servers)} replicas vs one "
          f"node on one chip: max error / max |ref| {err:.3e}; tolerance "
          f"{TOL:g}; the next request's row reads {wrong:.3e}")
    if err > TOL:
        raise Fail("replica outputs disagree with the one-chip run")
    if wrong <= TOL:
        raise Fail("a wrong answer reads within the tolerance")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the replicas-behind-the-router phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_info()
    print(f"device: {dev}", flush=True)
    if dev["platform"] != "tpu":
        raise Fail(f"no TPU: JAX runs on {dev['platform']}")
    if dev["count"] < args.chips:
        raise Fail(f"--chips {args.chips} but {dev['count']} device(s)")
    print(f"compile cache: {use_compile_cache()}")
    arch = get_arch(ARCH)
    cfg = arch.make_config()
    print(f"config: {cfg.name} img_res={cfg.img_res} patch={cfg.patch} "
          f"d_model={cfg.d_model} n_heads={cfg.n_heads} d_ff={cfg.d_ff} "
          f"n_layers={cfg.n_layers} tokens={cfg.n_tokens} "
          f"compute={cfg.compute_dtype}", flush=True)
    if args.chips == 4:
        serve_replicas(arch, cfg, jax.devices()[:4], args.seed)
    else:
        serve_governed(arch, cfg, args.seed)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
