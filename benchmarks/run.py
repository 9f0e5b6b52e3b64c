"""Benchmark harness — one module per paper result + the roofline table.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call is the natural
scalar of each row: wall-clock us, energy, %, or roofline time).

``--json PATH`` additionally writes the rows as machine-readable JSON
(``{"suites": {title: [{"name", "value", "derived"}]}, ...}``) so the
perf trajectory accumulates across PRs (BENCH_<n>.json files at the repo
root; BENCH_3.json records the bucketed-vs-padded serving comparison,
BENCH_4.json the cluster scale-out and p2c-vs-round-robin routing,
BENCH_5.json the calibration loop: closed-loop energy ratio and replay
p95-error ratio, BENCH_6.json the placement engine: rebalanced-vs-static
goodput under skew and the zero-migration steady-load guard,
BENCH_8.json the chaos day: reliability-on vs reliability-off goodput
under a rack failure + thermal + partition scenario, BENCH_9.json the
watchtower throttle day: alert-driven actuation vs reactive baseline
plus burn-rate attribution accuracy).

``--suite SUBSTR`` runs only the suites whose title contains SUBSTR —
the tier-1 smoke test uses it to gate the placement headline in seconds
instead of re-running every paper experiment.

``--compare PREV.json`` guards the trajectory: after the run, every
HEADLINE metric present in both the previous file and this run is
checked for a >10 % regression in its bad direction (goodput/speedups
falling, error/energy ratios rising) and the process exits non-zero if
any regressed — CI wires two invocations together as a perf gate.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

# Headline metrics --compare guards.  Deterministic (seeded virtual-time)
# metrics are gated RELATIVE to the previous file: a >tol move in the bad
# direction fails.  Live wall-clock ratios vary several-fold run to run
# (host contention), so prev-relative gating would false-flag honest
# runs — they are gated against an ABSOLUTE ceiling instead (the same
# invariant the bench itself asserts: calibrated must beat open-loop).
HEADLINES = {
    "traffic/serving_bucketed_speedup": {"direction": "higher",
                                         "tol": 0.10},
    "cluster/scale/2_node_speedup": {"direction": "higher", "tol": 0.10},
    "calibration/energy_ratio": {"max": 1.0},
    "calibration/p95_err_ratio": {"max": 1.0},
    "placement/rebalance_goodput_ratio": {"direction": "higher",
                                          "tol": 0.10},
    # absolute: steady load must NEVER migrate, in any mode
    "placement/steady_migrations": {"max": 0.0},
    # absolute floor: tracing-on goodput / tracing-off goodput
    "obs/trace_overhead_ratio": {"min": 0.97},
    # absolute floor: reliability-on goodput / reliability-off goodput
    # on the seeded chaos day (rack failure + thermal + partitions)
    "chaos/reliability_goodput_ratio": {"min": 1.5},
    # absolute: no request may ever vanish from the accounting, and
    # retries may never exceed the cluster budget allowance
    "chaos/lost_futures": {"max": 0.0},
    "chaos/retry_budget_frac": {"max": 1.0},
    # absolute floor: fired alerts whose attribution names the
    # injected root cause on the seeded throttle day
    "slo/attribution_accuracy": {"min": 0.8},
    # absolute floor: alert-driven actuation must not make the day
    # worse than the reactive baseline (time-in-SLO ratio)
    "slo/alerted_time_in_slo_ratio": {"min": 1.0},
    # absolute floor: guards-off goodput / uninstrumented goodput —
    # guarded_by declarations must be free when REPRO_GUARDS is unset
    "analysis/guard_overhead_ratio": {"min": 0.97},
}
REGRESSION_TOL = 0.10


def _flatten(suites: dict) -> dict:
    out = {}
    for rows in suites.values():
        for row in rows:
            out[row["name"]] = row["value"]
    return out


def compare_headlines(prev_suites: dict, new_suites: dict) -> list:
    """[(name, prev, new, why)] for every regressed headline metric."""
    prev = _flatten(prev_suites)
    new = _flatten(new_suites)
    regressions = []
    for name, spec in HEADLINES.items():
        if name not in new:
            continue
        n = new[name]
        if "max" in spec:
            if n > spec["max"]:
                regressions.append((name, prev.get(name), n,
                                    f"above absolute ceiling "
                                    f"{spec['max']:g}"))
            continue
        if "min" in spec:
            if n < spec["min"]:
                regressions.append((name, prev.get(name), n,
                                    f"below absolute floor "
                                    f"{spec['min']:g}"))
            continue
        if name not in prev:
            continue
        p = prev[name]
        tol = spec.get("tol", REGRESSION_TOL)
        direction = spec["direction"]
        if direction == "higher" and n < p * (1.0 - tol):
            regressions.append((name, p, n,
                                f"higher is better, tol {tol:.0%}"))
        elif direction == "lower" and n > p * (1.0 + tol):
            regressions.append((name, p, n,
                                f"lower is better, tol {tol:.0%}"))
    return regressions


def main() -> None:
    import benchmarks.bench_analysis as ban
    import benchmarks.bench_arbiter as ba
    import benchmarks.bench_calibration as bcal
    import benchmarks.bench_chaos as bch
    import benchmarks.bench_cluster as bc
    import benchmarks.bench_governor as bg
    import benchmarks.bench_kernels as bk
    import benchmarks.bench_obs as bo
    import benchmarks.bench_pareto as bp
    import benchmarks.bench_placement as bpl
    import benchmarks.bench_slo as bslo
    import benchmarks.bench_switching as bs
    import benchmarks.bench_traffic as bt
    import benchmarks.roofline_table as rt
    from repro.launch.cache import use_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast path for suites that support it")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write per-benchmark metrics as JSON")
    ap.add_argument("--compare", metavar="PREV_JSON", default=None,
                    help="exit non-zero on >10%% regression of any "
                         "headline metric vs a previous --json file")
    ap.add_argument("--suite", metavar="SUBSTR", default=None,
                    help="run only suites whose title contains SUBSTR")
    args = ap.parse_args()
    use_compile_cache()

    suites = [
        ("pareto (paper: Dynamic-OFA vs static)", bp.run),
        ("governor (paper: energy vs Linux governors)", bg.run),
        ("arbiter (multi-workload vs independent governors)", ba.run),
        ("traffic (SLO admission+preemption vs FIFO; bucketed vs padded)",
         lambda: bt.run(smoke=args.smoke)),
        ("cluster (multi-node scale-out, p2c vs round-robin, admission)",
         lambda: bc.run(smoke=args.smoke)),
        ("placement (rebalance vs static first-fit; no-flapping; "
         "autoscale)",
         lambda: bpl.run(smoke=args.smoke)),
        ("calibration (closed-loop measured planning vs open-loop)",
         lambda: bcal.run(smoke=args.smoke)),
        ("obs (tracing on vs off: goodput unchanged, decomposition)",
         lambda: bo.run(smoke=args.smoke)),
        ("chaos (seeded fault day: reliability on vs off)",
         lambda: bch.run(smoke=args.smoke)),
        ("slo (watchtower throttle day: alert-driven vs reactive)",
         lambda: bslo.run(smoke=args.smoke)),
        ("analysis (guarded-by assertions: off must be free)",
         lambda: ban.run(smoke=args.smoke)),
        ("switching (paper: runtime architecture switching)", bs.run),
        ("kernels (elastic matmul / flash attention)", bk.run),
        ("roofline (dry-run derived)", rt.rows),
    ]
    if args.suite:
        suites = [(title, fn) for title, fn in suites
                  if args.suite in title]
        if not suites:
            sys.exit(f"--suite {args.suite!r} matched no suite")
    failures = 0
    results = {}
    print("name,us_per_call,derived")
    for title, fn in suites:
        print(f"# --- {title}")
        try:
            rows = list(fn())
            for name, val, derived in rows:
                print(f"{name},{val:.3f},{derived}")
            results[title] = [{"name": name, "value": val,
                               "derived": str(derived)}
                              for name, val, derived in rows]
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema": 1, "smoke": args.smoke,
                       "failures": failures, "suites": results},
                      f, indent=1, sort_keys=True)
        print(f"# wrote {args.json}")
    if args.compare:
        with open(args.compare) as f:
            prev = json.load(f)
        regressions = compare_headlines(prev.get("suites", {}), results)
        for name, p, n, why in regressions:
            prev_s = "n/a" if p is None else f"{p:.3f}"
            print(f"# REGRESSION {name}: {prev_s} -> {n:.3f} ({why})")
        if regressions:
            sys.exit(2)
        print(f"# compare vs {args.compare}: no headline regression")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
