"""Readings that set a cell's correctness limit (not part of a run).

    python3 bench/limits.py --workload ofa-s.moving --seconds 6 \\
        --seeds 101,102,...

One process sets the cell up once; for each seed it swaps in that seed's
weights and inputs, drives a short window at the cell's own load, and
takes the sample a run compares.  Per seed it prints the program's
reading (the cell's model family's ``rel_err``, worst over the sample)
and the control's: the family's plain reference at the precision below
the served one (``fp8=True``; for ``vit``, float8 e4m3 matrix products),
on the same inputs and sub-networks.  The limit goes above the largest
program reading and below the smallest control reading (PERF.md gives
both).
"""
from __future__ import annotations

import json
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def readings(cell, session, run, seed: int) -> dict:
    win, model, fam = run.window, cell.config["model"], cell.family
    prog, ctrl = 0.0, 0.0
    for name, ks in harness.sample_answers(cell, win, seed).items():
        x = session.pool[win.idx[ks]]
        ref = fam.reference(session.w, x, model, name)
        ys = [win.out[k] for k in ks]
        prog = max(prog, float(np.max(fam.rel_err(ys, ref))))
        c = fam.reference(session.w, x, model, name, fp8=True)
        ctrl = max(ctrl, float(np.max(fam.rel_err(c, ref))))
    return {"seed": seed, "program": prog, "control_fp8": ctrl,
            "failed": int(np.count_nonzero(win.failed)),
            "subnets": sorted(set(s for s in win.subnet if s))}


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.find_cell(args.workload)
    session = harness.Session(cell, seeds[0], False, T_START)
    rows = []
    for seed in seeds:
        session.reseed(seed)
        run = session.drive(args.seconds, seed)
        rows.append(readings(cell, session, run, seed))
        print(json.dumps(rows[-1]), flush=True)
    session.close()
    print(json.dumps({
        "program_max": max(r["program"] for r in rows),
        "control_min": min(r["control_fp8"] for r in rows)}))


if __name__ == "__main__":
    main()
