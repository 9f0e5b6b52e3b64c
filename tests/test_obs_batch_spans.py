"""The engine's ``batch`` span: one per dispatched batch, recorded through
the shared Tracer with every stage stamp, the requests' submit and take
times and the collector's CPU time over stack + dispatch; nothing of it
with no tracer attached.  Also the tracer's bounded batch buffer, the
process-wide :func:`current_tracer` handle, slotted spans sharing one
empty attrs mapping, and RT004 over ``tracer.batch`` call sites."""
import textwrap
import time

import jax
import numpy as np
import pytest

from repro.analysis.lint import lint_file
from repro.obs import (BATCH, SCHEMA, RequestTrace, Span, Tracer,
                       current_tracer, validate_schema)
from repro.obs import trace as obs

STAMPS = ("t_take_first", "t_collect", "t_dispatch", "t_disp_ret",
          "t_ready_dev", "t_ready", "t_done")
N_REQ = 13


def tiny_server(**kw):
    from repro.models.vit import ViTConfig, vit_apply, vit_init
    from repro.runtime import DynamicServer
    cfg = ViTConfig(name="t", img_res=16, patch=8, n_layers=2, d_model=32,
                    n_heads=4, d_ff=64, n_classes=4,
                    compute_dtype="float32")
    params = vit_init(jax.random.PRNGKey(0), cfg)
    dims = {"d_model": 32, "d_ff": 64, "n_heads": 4, "n_layers": 2}
    return DynamicServer(lambda p, x, E: vit_apply(p, x, cfg, E=E)[0],
                         params, dims, max_batch=4, timeout_ms=2.0, **kw)


def serve(server, n=N_REQ):
    x = np.zeros((16, 16, 3), "float32")
    server.start()
    try:
        futs = []
        for k in range(n):
            futs.append(server.submit(x))
            if k % 5 == 4:
                time.sleep(0.01)     # let some batches close part-full
        outs = [f.get(timeout=60) for f in futs]
    finally:
        server.stop()
    assert all(o.get("error") is None for o in outs)
    return outs


@pytest.fixture(scope="module")
def traced():
    tr = Tracer()
    server = tiny_server(tracer=tr)
    server.trace_node = "n0"
    serve(server)
    return tr, server


def test_one_batch_span_per_batch_with_ordered_stamps(traced):
    tr, server = traced
    batches = tr.batch_spans()
    stacks = {s.t0 for t in tr.requests() for s in t.spans
              if s.name == obs.STACK}
    assert len(batches) == len(stacks) >= 2
    assert sum(b.attrs["n"] for b in batches) == server.served == N_REQ
    for b in batches:
        a = b.attrs
        assert b.name == BATCH and b.node == a["node"] == "n0"
        assert b.t0 == a["t_take_first"] and b.t1 == a["t_done"]
        assert [a[k] for k in STAMPS] == sorted(a[k] for k in STAMPS)
        assert a["bucket"] >= a["n"] == len(a["submit"]) == len(a["take"])
        assert a["subnet"] == server.active_spec.name()
    assert tr.summary()["batches"] == len(batches)
    assert tr.summary()["batches_dropped"] == 0


def test_submit_and_take_are_the_request_queue_spans(traced):
    tr, _ = traced
    by_batch = {}
    for t in tr.requests():
        sp = {s.name: s for s in t.spans}
        by_batch.setdefault(sp[obs.STACK].t0, []).append(
            (sp[obs.QUEUE].t0, sp[obs.QUEUE].t1))
    for b in tr.batch_spans():
        pairs = sorted(zip(b.attrs["submit"], b.attrs["take"]))
        assert pairs == sorted(by_batch[b.attrs["t_collect"]])


def test_cpu_time_fits_in_its_wall_span(traced):
    tr, _ = traced
    for b in tr.batch_spans():
        a = b.attrs
        wall = a["t_disp_ret"] - a["t_collect"]
        assert 0.0 <= a["cpu_stack_s"] <= wall + 1e-6


def test_spans_meet_the_schema(traced):
    tr, _ = traced
    assert validate_schema(tr.spans() + tr.batch_spans()) == []
    assert SCHEMA[BATCH] == ("bucket", "subnet", "n")


def test_no_tracer_reads_no_cpu_clock_and_records_no_batch(monkeypatch):
    calls = {"thread_time": 0, "annotation": 0}
    thread_time = time.thread_time

    def counted():
        calls["thread_time"] += 1
        return thread_time()

    class Annotation:
        def __init__(self, *a, **kw):
            calls["annotation"] += 1

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(time, "thread_time", counted)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    bystander = Tracer()                 # built, never attached
    server = tiny_server()
    outs = serve(server, n=6)
    assert len(outs) == 6 and server.served == 6
    assert calls == {"thread_time": 0, "annotation": 0}
    assert bystander.batch_spans() == []
    # the same counters see a traced server's reads
    traced_server = tiny_server(tracer=bystander)
    serve(traced_server, n=6)
    n = len(bystander.batch_spans())
    assert n >= 1 and calls == {"thread_time": 2 * n, "annotation": n}


def test_batch_buffer_is_bounded():
    tr = Tracer(batch_cap=3)
    for k in range(5):
        tr.batch(float(k), k + 1.0, node=None, bucket=1, subnet="s", n=1)
    kept = tr.batch_spans()
    assert [s.t0 for s in kept] == [2.0, 3.0, 4.0]   # oldest evicted
    assert tr.batches_dropped == 2
    assert tr.summary()["batches"] == 3
    assert tr.summary()["batches_dropped"] == 2


def test_current_tracer_is_the_newest_built():
    a = Tracer()
    assert current_tracer() is a
    b = Tracer(clock=lambda: 0.0)
    assert current_tracer() is b and obs.current_tracer() is b


def test_spans_are_slotted_and_share_one_empty_attrs():
    tr = Tracer(clock=lambda: 0.0)
    tr.request("c", 0.0, 1.0, spans=[(obs.QUEUE, 0.0, 0.5, None),
                                     (obs.COLLECT, 0.5, 1.0, {})])
    tr.request("c", 1.0, 2.0, spans=[(obs.QUEUE, 1.0, 2.0, None)])
    spans = [s for t in tr.requests() for s in t.spans]
    spans.append(tr.decision(obs.HEALTH_FAIL, 0.0))
    assert all(s.attrs == {} for s in spans)
    assert len({id(s.attrs) for s in spans}) == 1
    with pytest.raises(TypeError):
        spans[0].attrs["x"] = 1          # shared, so read-only
    for obj in (spans[0], tr.requests()[0]):
        assert not hasattr(obj, "__dict__")
        with pytest.raises(AttributeError):
            obj.undeclared = 1
    assert isinstance(spans[0], Span)
    assert isinstance(tr.requests()[0], RequestTrace)


@pytest.mark.parametrize("call, fires", [
    ('self.tracer.batch(t0, t1, node=n, bucket=1, subnet="s", n=2)', False),
    ('self.tracer.batch(t0, t1, node=n, bucket=1, n=2)', True),
    ('tracer.batch(t0, t1, subnet="s")', True),
    ('self.tracer.batch(t0, t1, **attrs)', False),
    ('dataset.batch(32)', False),
])
def test_rt004_checks_batch_span_calls(tmp_path, call, fires):
    src = textwrap.dedent(f"""\
        def emit(self, tracer, dataset, t0, t1, n, attrs):
            {call}
        """)
    p = tmp_path / "snippet.py"
    p.write_text(src)
    rules = [f.rule for f in lint_file(str(p), "snippet.py")]
    assert rules == (["RT004"] if fires else [])
