"""Arithmetic several metric readers share."""
from __future__ import annotations

from typing import Optional


def host_ms_per_batch(run) -> Optional[float]:
    """Mean host time per batch in the engine: stacking the batch into
    its pad buffer plus the dispatch call (Tracer stack + dispatch
    spans), over batches stacked inside the window."""
    w = run.window
    bs = [b for b in run.batches if w.t0 <= b["t_stack"] <= w.t1]
    if not bs:
        return None
    return sum(b["stack_ms"] + b["dispatch_ms"] for b in bs) / len(bs)


def idle_pct(run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device, averaged over the cell's chips."""
    if run.profile is None:
        return None
    return 100.0 * (1.0 - run.profile["busy_s"] / run.profile["window_s"])


def served_flops(run, t0: float, t1: float) -> float:
    """Model FLOPs of the images answered inside [t0, t1]."""
    w = run.window
    total = 0.0
    per = {}
    for k, name in enumerate(w.subnet):
        if w.error[k] is not None or not (t0 <= w.reply[k] <= t1):
            continue
        if name not in per:
            per[name] = run.flops(name)
        total += per[name]
    return total


def full_width_roofline(run, log=None) -> Optional[float]:
    """Share of its roofline that the full-width executable with the most
    device time reached in the traced window: the least time the chip
    could take for one call (FLOPs over peak, or compulsory bytes over
    HBM bandwidth, whichever is larger, from the cell's model family)
    over its mean device time."""
    p = run.profile
    if p is None:
        return None
    fam = run.family
    full = [(v["device_s"], v) for v in p["programs"].values()
            if v["label"] and v["label"][0] == fam.FULL and v["runs"]]
    if not full:
        return None
    _, prog = max(full, key=lambda kv: kv[0])
    bucket = prog["label"][1]
    t_min, bound = fam.roofline_s(run.model, fam.FULL, bucket, run.peak)
    mean = prog["device_s"] / prog["runs"]
    if log is not None:
        log(f"full width bucket {bucket}: {prog['runs']} runs, mean "
            f"{mean * 1e3:.4f} ms on the device; roofline "
            f"{t_min * 1e3:.4f} ms, {bound}-bound")
    return 100.0 * t_min / mean
