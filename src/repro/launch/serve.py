"""Serving launcher: the paper's deployed system.

``python -m repro.launch.serve --arch dynamic-ofa-supernet --smoke``

Brings up the DynamicServer (sub-network executable cache + bucketed
continuous batching + pipelined dispatch) with the JointGovernor in the
loop, drives it with the paper's workload trace (changing latency
targets, thermal throttling, co-running apps) and prints the monitor
summary next to the Linux-governor baselines.

Serving data-path knobs (mirrored by ``DynamicServer``):

* ``--max-batch N``   — batching ceiling; the bucket ladder is the powers
  of two up to N (requests are padded only to the nearest bucket);
* ``--no-buckets``    — pad every batch to max_batch (old data path, the
  baseline ``bench_traffic`` compares against);
* ``--no-pipeline``   — dispatch synchronously instead of overlapping
  batch N+1's host-side stacking with batch N's device time.

Cluster / trace knobs (``--trace`` mode):

* ``--nodes N``       — scale the SLO classes out over N arbiter-governed
  nodes behind the cluster front-end (``repro.cluster``); node i serves
  from ``jax.devices()[i % n]``, so N one-chip replicas on an N-chip host
  each hold their own chip;
* ``--router p2c|round_robin|least_loaded`` — the routing policy;
* ``--record PATH``   — save the ACTUAL arrivals as a replayable
  schedule JSON (feed it back via ``--trace PATH``);
* ``--calibrate``     — close the measurement loop: servers record
  per-(subnet, bucket) latency EWMAs and measured tenant energy into a
  ``CalibrationStore`` the arbiter plans off (measured watts in the
  water-filling, calibrated LUT columns); ``--calibrate-out PATH``
  additionally saves the warmed store as JSON for calibrated replays;
* ``--health-interval S`` — cluster mode: run the stall-based health
  checker every S seconds (a node whose completions stay flat with
  futures outstanding is auto-failed over).

Observability (any mode):

* ``--trace-out PATH``   — record request span trees + decision spans
  through a :class:`repro.obs.Tracer` and write them as Chrome
  trace-event JSON (load in Perfetto / chrome://tracing); also prints
  the per-class p50/p95 latency decomposition;
* ``--metrics-out PATH`` — write the metrics registry snapshot
  (counters / gauges / histograms) as JSON, or Prometheus text format
  when PATH ends in ``.prom``.

The governed server warms its bucket ladder for the profiled subnets
before taking traffic, so steady-state serving performs zero cold
compiles (``server.cold_compiles`` stays 0).  The first line names the
device; compiled executables persist in the directory
``repro.launch.cache`` picks.  In plain mode a failed request fails the
run (non-zero exit).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.core.types import SubnetSpec
from repro.launch.cache import use_compile_cache
from repro.obs import (MetricsRegistry, TraceStreamer, Tracer, Watchtower,
                       decompose_latency, default_windows,
                       format_alerts, format_decomposition, quantile,
                       write_chrome_trace)
from repro.runtime import (CalibrationStore, Constraints, DynamicServer,
                           GlobalConstraints, JointGovernor, Monitor,
                           PerformanceGovernor, ResourceArbiter,
                           SchedutilGovernor, StaticPrunedGovernor,
                           measured_lut, model_lut, paper_trace,
                           run_governor)
from repro.runtime import hwmodel as hm


def device_info() -> dict:
    """The device this process serves on, as JAX reports it."""
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def build_server(arch, cfg, *, max_batch=8, batch_buckets=True,
                 pipeline=True, calibration=None, tenant=None, device=None):
    """One DynamicServer over seeded weights; ``device`` pins its params
    (and so every executable it dispatches) to that device."""
    key = jax.random.PRNGKey(0)
    if arch.arch_id.startswith(("deit", "vit", "dynamic-ofa")):
        from repro.models.vit import vit_apply, vit_init
        params = vit_init(key, cfg)
        if device is not None:
            params = jax.device_put(params, device)
        dims = {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
                "n_heads": cfg.n_heads, "n_layers": cfg.n_layers}
        apply_fn = lambda p, x, E: vit_apply(p, x, cfg, E=E)[0]
    else:
        raise SystemExit("serve launcher: vision transformer archs only "
                         "(the paper serves image classification)")
    return DynamicServer(apply_fn, params, dims, max_batch=max_batch,
                         batch_buckets=batch_buckets, pipeline=pipeline,
                         calibration=calibration, tenant=tenant)


def measure_lut(server, specs, x, hw_states=None):
    """LUT of ``specs`` measured on ``server`` at batch ``len(x)``.

    Each subnet is timed once at full frequency; every frequency tier
    scales that time by ``1 / hw.freq`` and prices it at the tier's
    modelled power, so the tiers of one subnet never disagree on rank."""
    ms = {}

    def measure(spec, hw):
        if spec not in ms:
            ms[spec] = server.measure(spec, x)
        lat = ms[spec] / hw.freq
        return lat, hm.step_energy_mj(hm.RooflineTerms(lat / 1e3, 0.0, 0.0),
                                      hw)
    return measured_lut(specs, measure, hw_states=hw_states)


def run_trace_mode(args, arch, cfg, server, lut, x, base_ms):
    """``--trace``: SLO-classed request streams through the arbiter.

    Two tenants (an interactive class and a background batch class) run
    as separate DynamicServers behind one ResourceArbiter; the traffic
    layer replays a seeded arrival schedule (or a recorded one from a
    JSON file) open-loop against them and reports per-class percentile
    latency, goodput and drops.  ``--nodes N`` scales the same classes
    out over N arbiter-governed nodes behind a ``--router`` cluster
    front-end; ``--record PATH`` saves the actual arrivals as a replayable
    schedule.
    """
    from repro.traffic import (DEGRADE, SLOClass, drive_live, load_schedule,
                               onoff, poisson)

    need_tracer = args.trace_out or args.stream_trace or args.alerts_out
    tracer = Tracer() if need_tracer else None
    metrics = (MetricsRegistry()
               if (args.metrics_out or args.alerts_out) else None)
    dur = args.trace_duration
    streamer = (TraceStreamer(args.stream_trace).attach(tracer)
                if args.stream_trace else None)
    watchtower = None
    if args.alerts_out:
        # burn windows scaled so the trace duration is one SLO day; the
        # live driver feeds/evaluates it as futures resolve
        watchtower = Watchtower(
            {"interactive": 0.99, "batch": 0.95},
            windows=default_windows(dur / 86400.0),
            tracer=tracer, registry=metrics, hist_name="engine_request_ms")
    rate = args.requests / dur
    a_batch = poisson(max(rate / 2, 0.5), dur, seed=1)
    if args.trace == "poisson":
        a_int = poisson(rate, dur, seed=0)
    elif args.trace == "bursty":
        a_int = onoff(2.0 * rate, dur, on_s=dur / 6, off_s=dur / 6, seed=0)
    elif args.trace == "diurnal":
        from repro.traffic import diurnal
        a_int = diurnal(2.0 * rate, dur, period_s=dur / 2, seed=0)
    else:
        loaded = load_schedule(args.trace)   # recorded schedule replay
        if isinstance(loaded, dict):
            # multi-stream recording (drive_live --record): replay every
            # class it holds, falling back to the defaults for the rest
            a_int = loaded.get("interactive", poisson(rate, dur, seed=0))
            a_batch = loaded.get("batch", a_batch)
        else:
            a_int = loaded

    classes = [
        SLOClass("interactive", deadline_ms=base_ms * 8, priority=2),
        SLOClass("batch", deadline_ms=base_ms * 30, priority=0,
                 drop_policy=DEGRADE),
    ]
    streams = {"interactive": a_int, "batch": a_batch}
    # warm each bucket ladder for every profiled subnet (the arbiter's
    # governors pick from the LUT): the live trace pays zero cold compiles
    warm = list(dict.fromkeys(p.subnet for p in lut.points))
    store = CalibrationStore() if args.calibrate else None

    if args.nodes > 1:
        from repro.cluster import Cluster, ClusterNode
        devices = jax.devices()
        nodes = [ClusterNode(name=f"node{i}",
                             g_fn=lambda t: GlobalConstraints(total_chips=2))
                 for i in range(args.nodes)]
        cluster = Cluster(nodes, router=args.router,
                          health_interval_s=args.health_interval,
                          rebalance_interval_s=args.rebalance_interval,
                          tracer=tracer, metrics=metrics)
        if store is not None:
            for node in nodes:
                node.arbiter.calibration = store

        for c in classes:
            def mk_server(node, _name=c.name):
                # node i serves from device i (mod the devices present)
                dev = devices[nodes.index(node) % len(devices)]
                s = build_server(arch, cfg, max_batch=server.max_batch,
                                 batch_buckets=server.batch_buckets,
                                 pipeline=server.pipeline,
                                 calibration=store, tenant=_name, device=dev)
                s.warm(warm, example_input=x[0])
                return s

            placed = cluster.register(c.name, lut,
                                      target_latency_ms=c.service_target_ms,
                                      priority=c.priority,
                                      make_server=mk_server)
            print(f"  {c.name}: placed on {placed}")
        report = drive_live(
            classes, cluster.ports(), cluster, streams, lambda name: x[0],
            g_fn=lambda: GlobalConstraints(total_chips=2),
            record_path=args.record, watchtower=watchtower)
        print(f"\ncluster trace mode [{args.trace}] x{args.nodes} nodes, "
              f"router={args.router}: {len(a_int)} interactive + "
              f"{len(a_batch)} batch arrivals over {dur:.1f}s")
        for name, cs in report.classes.items():
            print(f"  {name:12s} {cs.summary()}")
        print(f"  routed       {report.arbiter['routed']}")
        if args.health_interval is not None:
            print(f"  health-failed nodes: "
                  f"{report.arbiter.get('health_failed', [])}")
        if args.rebalance_interval is not None:
            print(f"  migrations:   {report.arbiter.get('migrations', [])}")
            print(f"  preempted:    {report.arbiter.get('preempted', [])}")
        _report_calibration(store, args)
        _emit_obs(args, tracer, cluster.metrics, watchtower=watchtower,
                  streamer=streamer)
        return

    batch_server = build_server(arch, cfg, max_batch=server.max_batch,
                                batch_buckets=server.batch_buckets,
                                pipeline=server.pipeline,
                                calibration=store, tenant="batch")
    if store is not None:
        # the profiling server becomes the interactive tenant: tag it so
        # its measured energy lands under the right calibration row
        server.calibration, server.tenant = store, "interactive"
    servers = {"interactive": server, "batch": batch_server}
    for s in servers.values():
        s.warm(warm, example_input=x[0])
    arbiter = ResourceArbiter(interval_s=0.05, calibration=store,
                              tracer=tracer, metrics=metrics)
    for c in classes:
        # two modelled 1-chip slices: the measured LUT profiles chips=1,
        # so a 2-chip pool lets both tenants hold a slice at once
        arbiter.register(c.name, lut, target_latency_ms=c.service_target_ms,
                         priority=c.priority, server=servers[c.name])
    report = drive_live(
        classes, servers, arbiter, streams, lambda name: x[0],
        g_fn=lambda: GlobalConstraints(total_chips=2),
        record_path=args.record, tracer=tracer, metrics=metrics,
        watchtower=watchtower)
    print(f"\ntrace mode [{args.trace}] {len(a_int)} interactive + "
          f"{len(a_batch)} batch arrivals over {dur:.1f}s")
    for name, cs in report.classes.items():
        print(f"  {name:12s} {cs.summary()}")
    print(f"  arbiter      {report.arbiter}")
    if args.record:
        print(f"  recorded actual arrivals -> {args.record}")
    _report_calibration(store, args)
    _emit_obs(args, tracer, arbiter.metrics, watchtower=watchtower,
              streamer=streamer)


def _emit_obs(args, tracer, metrics, watchtower=None, streamer=None):
    """Write --trace-out / --metrics-out / --alerts-out artifacts, close
    the --stream-trace stream, and print the per-class latency
    decomposition for the retained traces."""
    if streamer is not None:
        n = streamer.close(tracer)
        print(f"  streamed {n} trace events -> {streamer.path}")
    if tracer is not None and args.trace_out:
        n = write_chrome_trace(tracer, args.trace_out)
        print(f"  trace: {len(tracer.requests())} request trees retained "
              f"({tracer.dropped} evicted), {n} events -> {args.trace_out}")
        decomp = decompose_latency(tracer)
        if decomp:
            print(format_decomposition(decomp))
    if watchtower is not None and args.alerts_out:
        with open(args.alerts_out, "w") as f:
            text = format_alerts(watchtower.alerts)
            f.write(text + ("\n" if text else ""))
        print(f"  {len(watchtower.alerts)} SLO alerts "
              f"(time-in-SLO {watchtower.summary()['time_in_slo']}) "
              f"-> {args.alerts_out}")
    if metrics is not None and args.metrics_out:
        text = (metrics.to_prometheus()
                if args.metrics_out.endswith(".prom")
                else metrics.to_json())
        with open(args.metrics_out, "w") as f:
            f.write(text)
        print(f"  metrics snapshot -> {args.metrics_out}")


def _report_calibration(store, args):
    if store is None:
        return
    s = store.summary()
    print(f"  calibration: {len(s['latency'])} (subnet, bucket) latency "
          f"columns, power rows: {s['power']}")
    if args.calibrate_out:
        store.save(args.calibrate_out)
        print(f"  calibration store saved -> {args.calibrate_out}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dynamic-ofa-supernet")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--trace-steps", type=int, default=200)
    ap.add_argument("--trace", default=None,
                    help="SLO traffic mode: poisson | bursty | diurnal | "
                         "path to a recorded schedule JSON")
    ap.add_argument("--trace-duration", type=float, default=5.0,
                    help="seconds of arrival schedule in --trace mode")
    ap.add_argument("--nodes", type=int, default=1,
                    help="cluster mode: N arbiter-governed nodes behind "
                         "the router (--trace only)")
    ap.add_argument("--router", default="p2c",
                    choices=["p2c", "round_robin", "least_loaded"],
                    help="cluster routing policy for --nodes > 1")
    ap.add_argument("--record", default=None, metavar="PATH",
                    help="record the ACTUAL --trace arrivals to a "
                         "replayable schedule JSON")
    ap.add_argument("--calibrate", action="store_true",
                    help="close the measurement loop: record measured "
                         "(subnet, bucket) latency + tenant energy and "
                         "let the arbiter plan off it")
    ap.add_argument("--calibrate-out", default=None, metavar="PATH",
                    help="save the warmed CalibrationStore as JSON "
                         "(implies nothing without --calibrate)")
    ap.add_argument("--health-interval", type=float, default=None,
                    metavar="S",
                    help="cluster mode: stall-based health check every "
                         "S seconds (auto-failover of wedged nodes)")
    ap.add_argument("--rebalance-interval", type=float, default=None,
                    metavar="S",
                    help="cluster mode: run the global placement engine "
                         "every S seconds (migration-cost-priced replica "
                         "rebalancing + cross-node preemption)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record request span trees + decision spans and "
                         "write Chrome trace-event JSON (open in Perfetto "
                         "or chrome://tracing); prints the p50/p95 "
                         "latency decomposition")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics snapshot as JSON (Prometheus "
                         "text format when PATH ends in .prom)")
    ap.add_argument("--stream-trace", default=None, metavar="PATH",
                    help="stream trace events to PATH as requests retire "
                         "(incremental Perfetto JSON — loadable mid-run "
                         "or after a crash)")
    ap.add_argument("--alerts-out", default=None, metavar="PATH",
                    help="--trace mode: run the SLO watchtower (burn-rate "
                         "alerts + attribution) against the live run and "
                         "write the alert log to PATH")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="batching ceiling (bucket ladder = powers of two)")
    ap.add_argument("--no-buckets", action="store_true",
                    help="pad every batch to max_batch (baseline data path)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="synchronous dispatch (no host/device overlap)")
    args = ap.parse_args(argv)

    use_compile_cache()
    print(f"device: {device_info()}")
    arch = get_arch(args.arch)
    cfg = arch.make_smoke() if args.smoke else arch.make_config()
    server = build_server(arch, cfg, max_batch=args.max_batch,
                          batch_buckets=not args.no_buckets,
                          pipeline=not args.no_pipeline)

    # Pareto subnets of the elastic space
    specs = list(dict.fromkeys(
        [cfg.elastic.max_spec(), cfg.elastic.min_spec()]
        + list(cfg.elastic.enumerate(limit=24))))
    x = np.random.default_rng(0).normal(
        size=(server.max_batch, cfg.img_res, cfg.img_res, 3)).astype(np.float32)

    # measured LUT on this host (freq modelled; latency real wall-clock)
    lut = measure_lut(server, specs, x)
    print(f"profiled {len(lut.points)} operating points over "
          f"{len(specs)} subnets")

    full = SubnetSpec()
    base_ms = np.median([p.latency_ms for p in lut.points
                         if p.subnet == full])
    if args.trace:
        run_trace_mode(args, arch, cfg, server, lut, x, base_ms)
        return
    governors = {
        "joint (paper)": JointGovernor(lut),
        "performance": PerformanceGovernor(lut, full),
        "schedutil": SchedutilGovernor(lut, full),
        "static-pruned": StaticPrunedGovernor(
            lut, worst_case=Constraints(target_latency_ms=base_ms * 0.8,
                                        chips_available=1)),
    }
    print(f"\nworkload trace: {args.trace_steps} steps, base target "
          f"{base_ms:.2f}ms")
    for name, gov in governors.items():
        mon = run_governor(gov, paper_trace(args.trace_steps, chips=1,
                                            base_target_ms=base_ms))
        print(f"  {name:16s} {mon.summary()}")

    # serve real batched requests through the governor; warm the bucket
    # ladder for every profiled subnet (anything the governor may pick)
    # so steady state starts compile-free
    gov = governors["joint (paper)"]
    constraints = lambda: Constraints(target_latency_ms=base_ms,
                                      chips_available=1)
    server.governor = gov
    tracer = Tracer() if (args.trace_out or args.stream_trace) else None
    metrics = MetricsRegistry() if args.metrics_out else None
    streamer = (TraceStreamer(args.stream_trace).attach(tracer)
                if args.stream_trace else None)
    if tracer is not None:
        server.tracer = tracer
    if metrics is not None:
        server.metrics = metrics
    server.warm(specs, example_input=x[0])
    server.start(constraints_fn=constraints)
    futs = [server.submit(x[0]) for _ in range(args.requests)]
    outs = [f.get(timeout=30) for f in futs]
    server.stop()
    # the engine answers a failed batch or dispatch with an error payload
    # so callers never hang; here a failure must fail the run
    errors = [o["error"] for o in outs if o.get("error")]
    if errors:
        raise SystemExit(f"{len(errors)}/{len(outs)} requests failed: "
                         f"{errors[0]}")
    lats = [o["latency_ms"] for o in outs]
    print(f"\nserved {len(outs)} requests  p50={quantile(lats,50):.1f}ms "
          f"p99={quantile(lats,99):.1f}ms  "
          f"subnets used: {sorted(set(o['subnet'] for o in outs))}")
    print(f"switches: {len(server.switch_log)} "
          f"(dropped {server.switch_log_dropped} log entries), "
          f"cold compiles while serving: {server.cold_compiles}, "
          f"buckets: {server.buckets}, pipeline: {server.pipeline}")
    _emit_obs(args, tracer, metrics, streamer=streamer)


if __name__ == "__main__":
    main()
