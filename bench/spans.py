"""Arithmetic of the readers of the engine's ``batch`` spans: how long
requests queued, how much of stack + dispatch the collector thread spent
on the CPU, and the device's idle time split by what its engine was
doing.

The spans come from the process's newest Tracer
(``repro.obs.trace.current_tracer``), the one the harness attached to
the servers; a program that records none reads nothing, and every
reader here then returns None.

Idle split, per chip on the device clock (the harness's fitted offset,
device minus ``perf_counter``, puts the spans there): of the traced
window's idle time (no op on the device),

* ``host``: its engine held a request on the host, a queue wait
  ``[t_submit, t_take)`` or a stack + dispatch call
  ``[t_collect, t_disp_ret)``, and no batch was awaiting the device;
* ``await``: a dispatched batch was not yet device-ready,
  ``[t_disp_ret, t_ready_dev)``: the input copy and runtime queueing;
* ``no_work``: the rest, so the three sum to the idle share.
"""
from __future__ import annotations

import json
from typing import Callable, Dict, List, Optional, Sequence

from bench import stats, trace_reduce

# where the harness keeps a traced run's condensed profile
CONDENSED = ("bench", "out", "trace", "condensed.json")


def batch_spans() -> Optional[List[dict]]:
    """The attrs of every retained ``batch`` span, or None where the
    program records none."""
    try:
        from repro.obs.trace import current_tracer
    except ImportError:
        return None
    read = getattr(current_tracer(), "batch_spans", None)
    if read is None:
        return None
    return [s.attrs for s in read()] or None


def stacked_in_window(run) -> Optional[List[dict]]:
    """Batches whose stacking began inside the measured window."""
    spans = batch_spans()
    if spans is None:
        return None
    w = run.window
    return [b for b in spans if w.t0 <= b["t_collect"] <= w.t1] or None


def queue_ms_p99(run) -> Optional[float]:
    """Nearest-rank p99 of take minus submit, in ms, over the requests of
    the batches stacked inside the window."""
    bs = stacked_in_window(run)
    if bs is None:
        return None
    return stats.nearest_rank([(tk - sb) * 1e3 for b in bs
                               for sb, tk in zip(b["submit"], b["take"])], 99)


def host_cpu_pct(run) -> Optional[float]:
    """The collector thread's CPU time over the wall time of stack +
    dispatch, summed over the batches stacked inside the window."""
    bs = stacked_in_window(run)
    if bs is None:
        return None
    wall = sum(b["t_disp_ret"] - b["t_collect"] for b in bs)
    if wall <= 0:
        return None
    return 100.0 * sum(b["cpu_stack_s"] for b in bs) / wall


# --- interval arithmetic on sorted, disjoint [start, end] lists -------------

def measure(ivs) -> float:
    return sum(e - s for s, e in ivs)


def gaps(busy, w0: float, w1: float) -> List[List[float]]:
    """[w0, w1] less the merged ``busy`` intervals inside it."""
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [[edges[k], edges[k + 1]] for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]


def overlap(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def subtract(a, b) -> List[List[float]]:
    """The merged intervals ``a`` less the merged intervals ``b``."""
    out: List[List[float]] = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


# --- the idle split ----------------------------------------------------------

def split_idle(condensed: dict, offsets: Sequence[float],
               window_perf: Sequence[float], spans: List[dict],
               chips: int) -> List[Dict[str, float]]:
    """Per chip: the traced window's length and its idle, host-held and
    awaiting seconds (see the module's docstring).  Chip ``i`` serves the
    batches of node ``node<i>``, or of node None where there is one."""
    out = []
    for i in range(chips):
        off = offsets[i]
        node = f"node{i}" if chips > 1 else None
        mine = [b for b in spans if b["node"] == node]

        def dev(t0, t1):
            return (t0 + off) * 1e9, (t1 + off) * 1e9

        w0, w1 = dev(*window_perf)
        ops = condensed["devices"][str(i)]["ops"]
        busy = trace_reduce.union(trace_reduce.clip(
            [(s, s + d) for _, s, d in ops], w0, w1))
        idle = gaps(busy, w0, w1)
        waiting = trace_reduce.union(trace_reduce.clip(
            [dev(b["t_disp_ret"], b["t_ready_dev"]) for b in mine], w0, w1))
        held = [dev(s, t) for b in mine
                for s, t in zip(b["submit"], b["take"])]
        held += [dev(b["t_collect"], b["t_disp_ret"]) for b in mine]
        held = subtract(trace_reduce.union(trace_reduce.clip(held, w0, w1)),
                        waiting)
        out.append({"window_s": (w1 - w0) * 1e-9,
                    "idle_s": measure(idle) * 1e-9,
                    "host_s": overlap(idle, held) * 1e-9,
                    "await_s": overlap(idle, waiting) * 1e-9})
    return out


def split_pct(parts: List[Dict[str, float]]) -> Dict[str, float]:
    """Shares of the window, in %, averaged over the chips."""
    def pct(key):
        return sum(100.0 * p[key] / p["window_s"] for p in parts) / len(parts)
    idle, host, wait = pct("idle_s"), pct("host_s"), pct("await_s")
    return {"idle": idle, "host": host, "await": wait,
            "no_work": idle - host - wait}


def idle_split(run, log: Optional[Callable[[str], None]] = None
               ) -> Optional[Dict[str, float]]:
    """The traced run's idle split in % of the window, mean over its
    chips; logs each chip's split and the sum beside ``device.idle_pct``."""
    p = run.profile
    spans = batch_spans()
    if p is None or spans is None:
        return None
    path = run.cell.root.joinpath(*CONDENSED)
    if not path.is_file():
        return None
    condensed = json.loads(path.read_text())
    parts = split_idle(condensed, p["offsets_s"], run.trace_window, spans,
                       run.chips)
    split = split_pct(parts)
    if log is not None:
        for i, part in enumerate(parts):
            s = split_pct([part])
            log(f"idle split, chip {i}: host {s['host']:.4f}% + await "
                f"{s['await']:.4f}% + no work {s['no_work']:.4f}% = idle "
                f"{s['idle']:.4f}%")
        log(f"idle split, mean: host {split['host']:.4f}% + await "
            f"{split['await']:.4f}% + no work {split['no_work']:.4f}% = "
            f"{split['host'] + split['await'] + split['no_work']:.4f}%; "
            f"device.idle_pct "
            f"{100.0 * (1.0 - p['busy_s'] / p['window_s']):.4f}%")
    return split
