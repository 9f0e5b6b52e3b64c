"""End-to-end metric arithmetic on records made by hand."""
import math
import types

import numpy as np
import pytest

from bench import loop, stats
from bench.families import vit
from bench.harness import ROOT, Run, load_module


def reader(name):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


def window(due, reply, errors=None, subnets=None):
    n = len(due)
    return loop.Window(
        t0=0.0, t1=10.0, due=np.asarray(due, float),
        sent=np.asarray(due, float), reply=np.asarray(reply, float),
        idx=np.zeros(n, np.int64), out=[None] * n,
        subnet=subnets or ["w1-f1-h1-d1"] * n,
        error=errors or [None] * n)


def run_of(win, deadline=None, seconds=10.0):
    cell = types.SimpleNamespace(family=vit, config={"model": {
        "img_res": 224, "patch": 16, "n_layers": 12, "d_model": 384,
        "n_heads": 6, "d_ff": 1536, "n_classes": 1000,
        "distill_token": False}})
    return Run(cell=cell, window=win, seconds=seconds, chips=1, peak={},
               setup_s=1.0, deadline_ms=deadline, switches=0, served=[],
               batches=[])


def test_nearest_rank():
    assert stats.nearest_rank([3, 1, 2, 4], 50) == 2
    assert stats.nearest_rank(list(range(1, 101)), 95) == 95
    assert stats.nearest_rank([5.0], 99) == 5.0


def test_p95_counts_failures_as_misses():
    """The tail ``bench/sweep.py`` prints per rate: a failed or unanswered
    request is infinitely late."""
    def p95(win):
        lat = stats.latencies_ms(win.due, np.where(win.failed, np.nan,
                                                   win.reply))
        return stats.nearest_rank(lat, 95)
    # 20 requests 10 ms late, then one unanswered and one failed
    due = np.arange(20) * 0.1
    assert p95(window(due, due + 0.010)) == pytest.approx(10.0)
    due2 = np.arange(22) * 0.1
    reply = np.concatenate([due + 0.010, [math.nan, due2[21] + 0.001]])
    errors = [None] * 20 + ["no reply", "batch failed"]
    # 2 of 22 are infinitely late: the 95th percentile (rank 21) is one
    assert p95(window(due2, reply, errors)) == math.inf


def test_goodput_against_due_time_deadlines():
    due = np.array([0.0, 1.0, 2.0, 3.0])
    reply = due + np.array([0.004, 0.012, 0.030, 0.009])
    deadline = np.array([5.0, 10.0, 40.0, 8.0])   # ms, per request
    got = reader("goodput_rps")(run_of(window(due, reply), deadline, 2.0))
    assert got == pytest.approx(2 / 2.0)     # the first and the third


def test_goodput_is_from_due_time_not_send_time():
    due = np.array([0.0])
    win = window(due, due + 0.008)
    win.sent = due + 0.006         # the generator ran 6 ms late
    assert reader("goodput_rps")(run_of(win, np.array([5.0]), 1.0)) == 0


def test_closed_loop_images_per_s():
    # replies inside [0, 10] count; one after the window, one failed
    due = np.zeros(5)
    reply = np.array([1.0, 2.0, 9.9, 10.5, 3.0])
    errors = [None, None, None, None, "batch failed"]
    got = reader("images_per_s")(run_of(window(due, reply, errors)))
    assert got == pytest.approx(3 / 10.0)


def test_top1_surrogate_of_served_subnets():
    due = np.zeros(2)
    win = window(due, due + 0.01,
                 subnets=["w1-f1-h1-d1", "w1-f0.5-h1-d0.666667"])
    got = reader("top1_surrogate_pct")(run_of(win))
    small = 80.0 + 9.6 * math.log10(4.3103e9 / 9.2e9)
    assert got == pytest.approx((80.0 + small) / 2, abs=0.05)


def test_seed32_accepts_large_and_negative_seeds():
    a = stats.seed32(2**31 + 12345, 1)
    assert 0 <= a < 2**32 and a == stats.seed32(2**31 + 12345, 1)
    assert stats.seed32(-5, 1) != stats.seed32(5, 1)
    assert stats.seed32(7, 1) != stats.seed32(7, 2)


def test_closed_loop_replaces_each_reply_as_it_comes():
    """Two replicas, one ten times slower: the fast one's replies are
    stamped when they come and replaced at once, not behind the slow
    one's in send order."""
    import queue
    import threading

    from bench.harness import ROOT, load_module
    closed = load_module(ROOT / "bench" / "traffic" / "closed.py")
    sent = [0]

    def submit(x):
        lane = sent[0] % 2
        sent[0] += 1
        fut = queue.Queue(maxsize=1)
        threading.Timer(0.05 if lane else 0.005, fut.put,
                        args=({"y": np.zeros(3), "subnet": "s"},)).start()
        return lane, fut

    pool = np.zeros((4, 2, 2, 3), np.float32)
    win = closed.drive({"outstanding": 4, "grace_s": 5}, submit, pool,
                       seconds=0.5, seed=1, lanes=2)
    lat = win.reply - win.sent
    assert not win.failed.any() and sent[0] == len(lat) > 20
    fast, slow = lat[0::2], lat[1::2]      # requests alternate lanes
    assert np.all(fast < 0.03), fast
    assert np.all(slow > 0.04), slow
