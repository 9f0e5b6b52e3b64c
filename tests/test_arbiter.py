"""Multi-workload arbiter + the PR's bugfix regressions (throttled
fallback, thread-safe executable cache, mesh/hypothesis compat)."""
import threading

import numpy as np
import pytest

from repro.core.types import ElasticSpace, SubnetSpec
from repro.runtime import (Constraints, GlobalConstraints, JointGovernor,
                           ResourceArbiter, model_lut)
from repro.runtime import hwmodel as hm

TERMS = hm.RooflineTerms(t_compute=0.02, t_memory=0.008, t_collective=0.004)
SPACE = ElasticSpace(width_mults=(0.5, 0.75, 1.0), ffn_mults=(0.5, 1.0),
                     depth_mults=(0.5, 1.0))
HW_STATES = [hm.HwState(chips=c, freq=f) for c in (256, 128, 64, 32)
             for f in hm.FREQ_LADDER]


def make_lut(scale=1.0):
    terms = hm.RooflineTerms(TERMS.t_compute * scale, TERMS.t_memory * scale,
                             TERMS.t_collective * scale)
    return model_lut(SPACE.enumerate(), full_terms=terms, full_chips=256,
                     hw_states=HW_STATES)


# --- bugfix regressions -------------------------------------------------------

def test_infeasible_fallback_respects_throttle():
    """JointGovernor's degraded pick must not exceed the thermal cap."""
    lut = make_lut()
    gov = JointGovernor(lut)
    # impossible target => fallback path; throttle must still bind
    point = gov.select(Constraints(target_latency_ms=1e-6,
                                   chips_available=256,
                                   temperature_throttle=0.7))
    assert point.hw_state.freq <= 0.7
    capped = [p for p in lut.points if p.hw_state.chips <= 256
              and p.hw_state.freq <= 0.7]
    assert point.latency_ms == min(p.latency_ms for p in capped)


def test_infeasible_fallback_respects_power_grant():
    """The degraded pick must also stay inside an arbiter power grant."""
    lut = make_lut()
    gov = JointGovernor(lut)
    budget = 15000.0
    point = gov.select(Constraints(target_latency_ms=1e-6,
                                   chips_available=256,
                                   power_budget_w=budget))
    assert hm.slice_power_w(point.hw_state) <= budget


def test_lut_fastest_freq_cap_relaxed_only_when_empty():
    lut = make_lut()
    p = lut.fastest(256, max_freq=0.55)
    assert p.hw_state.freq <= 0.55
    # a cap below the whole ladder relaxes rather than erroring
    p = lut.fastest(256, max_freq=0.1)
    assert p is not None


def test_executable_cache_thread_safe():
    """Concurrent executable() calls (worker + sync callers + arbiter
    clock) must build each spec exactly once and never race."""
    import jax
    from repro.models.vit import ViTConfig, vit_apply, vit_init
    from repro.runtime import DynamicServer
    cfg = ViTConfig(name="t", img_res=16, patch=8, n_layers=2, d_model=32,
                    n_heads=4, d_ff=64, n_classes=4, compute_dtype="float32")
    params = vit_init(jax.random.PRNGKey(0), cfg)
    dims = {"d_model": 32, "d_ff": 64, "n_heads": 4, "n_layers": 2}
    server = DynamicServer(lambda p, x, E: vit_apply(p, x, cfg, E=E)[0],
                           params, dims)
    specs = [SubnetSpec(), SubnetSpec(width_mult=0.5),
             SubnetSpec(ffn_mult=0.5), SubnetSpec(depth_mult=0.5)]
    got = []
    errors = []

    def hammer():
        try:
            for _ in range(20):
                for s in specs:
                    got.append((s, id(server.executable(s))))
        except Exception as e:  # pragma: no cover - the regression
            errors.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(server._cache) == len(specs)
    by_spec = {}
    for s, fid in got:
        by_spec.setdefault(s, set()).add(fid)
    assert all(len(ids) == 1 for ids in by_spec.values())


def test_make_mesh_axis_types_auto():
    """make_mesh gives every axis the Auto type the sharding hints need."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_hypothesis_importable_everywhere():
    """Real package or the conftest shim — @given must run the test body."""
    from hypothesis import given, strategies as st
    ran = []

    @given(x=st.integers(1, 5), y=st.sampled_from(["a", "b"]))
    def prop(x, y):
        ran.append((x, y))
        assert 1 <= x <= 5 and y in ("a", "b")

    prop()
    assert ran


# --- arbiter unit tests -------------------------------------------------------

def test_two_workloads_ample_budget_both_meet():
    arb = ResourceArbiter()
    arb.register("a", make_lut(), target_latency_ms=40.0, priority=1)
    arb.register("b", make_lut(0.5), target_latency_ms=25.0, priority=0)
    allocs = arb.arbitrate(GlobalConstraints(total_chips=512))
    assert all(a.feasible for a in allocs.values())
    assert all(a.point.latency_ms <= t for a, t in
               [(allocs["a"], 40.0), (allocs["b"], 25.0)])
    # never oversubscribes
    assert sum(a.chips for a in allocs.values()) <= 512


def test_shrinking_budget_degrades_by_priority():
    """As the pool shrinks, the low-priority workload loses its target
    first; the high-priority one keeps meeting it as long as possible."""
    arb = ResourceArbiter()
    arb.register("hi", make_lut(), target_latency_ms=40.0, priority=2)
    arb.register("lo", make_lut(), target_latency_ms=40.0, priority=0)
    prev_hi = True
    for total in (512, 256, 128, 64, 32):
        allocs = arb.arbitrate(GlobalConstraints(total_chips=total))
        hi, lo = allocs["hi"], allocs["lo"]
        assert sum(a.chips for a in allocs.values()) <= total
        # priority order: lo never feasible while hi is not
        assert hi.feasible or not lo.feasible
        # monotone: hi doesn't regain feasibility as the pool shrinks
        assert prev_hi or not hi.feasible
        prev_hi = hi.feasible
    # at 64 chips the high-priority workload still meets; low starves
    allocs = arb.arbitrate(GlobalConstraints(total_chips=64))
    assert allocs["hi"].feasible and not allocs["lo"].feasible


def test_surplus_buys_accuracy_for_high_priority():
    arb = ResourceArbiter()
    arb.register("hi", make_lut(), target_latency_ms=40.0, priority=2)
    arb.register("lo", make_lut(), target_latency_ms=40.0, priority=0)
    tight = arb.arbitrate(GlobalConstraints(total_chips=128))
    roomy = arb.arbitrate(GlobalConstraints(total_chips=512))
    assert roomy["hi"].point.accuracy >= tight["hi"].point.accuracy
    # with surplus, hi runs a higher-accuracy point than its minimal share
    assert roomy["hi"].chips >= tight["hi"].chips


def test_power_budget_and_throttle_respected():
    arb = ResourceArbiter()
    arb.register("a", make_lut(), target_latency_ms=60.0, priority=1)
    arb.register("b", make_lut(), target_latency_ms=60.0, priority=0)
    g = GlobalConstraints(total_chips=512, power_budget_w=40000.0,
                          temperature_throttle=0.7)
    allocs = arb.arbitrate(g)
    assert sum(a.power_w for a in allocs.values()) <= 40000.0
    for a in allocs.values():
        if a.point is not None:
            assert a.point.hw_state.freq <= 0.7


def test_backlogged_tenant_gets_surplus_first():
    """Queue-depth-aware water-filling (ROADMAP item): with equal
    priorities, the surplus goes to the backlogged tenant as SPEED — it
    ends up on a faster point (and at least as many chips) than its
    backlog-free peer, instead of everyone buying accuracy."""
    arb = ResourceArbiter()
    arb.register("a", make_lut(), target_latency_ms=40.0, priority=1)
    arb.register("b", make_lut(), target_latency_ms=40.0, priority=1)
    g = GlobalConstraints(total_chips=512)
    base = arb.arbitrate(g)
    assert base["a"].feasible and base["b"].feasible
    arb.set_active("a", True, queue_depth=64, arrival_rate_rps=200.0)
    arb.set_active("b", True, queue_depth=0)
    allocs = arb.arbitrate(g)
    assert allocs["a"].feasible and allocs["b"].feasible
    assert allocs["a"].chips >= allocs["b"].chips
    # the backlogged tenant runs strictly faster than the accuracy-first
    # pick it got when no backlog was reported
    assert allocs["a"].point.latency_ms < base["a"].point.latency_ms
    # never oversubscribes
    assert sum(x.chips for x in allocs.values()) <= 512


def test_backlog_ewma_smooths_arrival_rate():
    arb = ResourceArbiter()
    w = arb.register("a", make_lut(), target_latency_ms=40.0)
    arb.set_active("a", True, arrival_rate_rps=100.0)
    first = w.arrival_ewma
    assert 0.0 < first < 100.0              # smoothed, not raw
    arb.set_active("a", True, arrival_rate_rps=100.0)
    assert first < w.arrival_ewma < 100.0   # converging toward the rate


def test_server_queue_depth_feeds_arbiter():
    """A live tenant's backlog is read off its server automatically."""
    arb = ResourceArbiter()
    server = tiny_server()
    w = arb.register("a", make_lut(), target_latency_ms=40.0, server=server)
    x = np.zeros((16, 16, 3), "float32")
    futs = [server.submit(x) for _ in range(5)]   # queued: never started
    arb.arbitrate(GlobalConstraints(total_chips=256))
    assert w.queue_depth == 5
    server.stop()                                 # drains the futures
    for f in futs:
        assert f.get(timeout=5)["cancelled"]


def test_constraints_carry_priority_and_share():
    arb = ResourceArbiter()
    w = arb.register("a", make_lut(), target_latency_ms=40.0, priority=3)
    g = GlobalConstraints(total_chips=256)
    alloc = arb.arbitrate(g)["a"]
    c = arb.constraints_for(w, alloc, g)
    assert c.priority == 3
    assert c.share == pytest.approx(alloc.chips / 256)
    assert c.chips_available == alloc.chips


def test_duplicate_registration_rejected():
    arb = ResourceArbiter()
    arb.register("a", make_lut(), target_latency_ms=40.0)
    with pytest.raises(ValueError):
        arb.register("a", make_lut(), target_latency_ms=40.0)


def tiny_server():
    import jax
    from repro.models.vit import ViTConfig, vit_apply, vit_init
    from repro.runtime import DynamicServer
    cfg = ViTConfig(name="t", img_res=16, patch=8, n_layers=2,
                    d_model=32, n_heads=4, d_ff=64, n_classes=4,
                    compute_dtype="float32")
    params = vit_init(jax.random.PRNGKey(0), cfg)
    dims = {"d_model": 32, "d_ff": 64, "n_heads": 4, "n_layers": 2}
    return DynamicServer(lambda p, x, E: vit_apply(p, x, cfg, E=E)[0],
                         params, dims)


def test_tick_drives_governors_and_servers():
    """Multi-server mode: one tick arbitrates and switches each server's
    active sub-network via its own governor."""
    arb = ResourceArbiter()
    s1, s2 = tiny_server(), tiny_server()
    arb.register("hi", make_lut(), target_latency_ms=40.0, priority=2,
                 server=s1)
    arb.register("lo", make_lut(), target_latency_ms=40.0, priority=0,
                 server=s2)
    allocs = arb.tick(GlobalConstraints(total_chips=256))
    for name, server in (("hi", s1), ("lo", s2)):
        if allocs[name].point is not None:
            assert server.active_spec == allocs[name].point.subnet \
                or server.active_point is not None
    # servers answer correctly after the arbiter-driven switch
    x = np.zeros((2, 16, 16, 3), "float32")
    assert s1.infer(x).shape == (2, 4)
    assert s2.infer(x).shape == (2, 4)
    assert len(arb.alloc_log) == 1
    summ = arb.summary()
    assert summ["hi"]["cycles"] == 1
    # starvation parks the low-priority server; recovery resumes it
    allocs = arb.tick(GlobalConstraints(total_chips=64))
    assert not allocs["lo"].feasible
    assert s2._paused.is_set() and not s1._paused.is_set()
    arb.tick(GlobalConstraints(total_chips=256))
    assert not s2._paused.is_set()
    assert arb.summary()["hi"]["cycles"] == 3


def test_server_restart_clears_pause():
    """A server stopped while starved must not come back parked."""
    server = tiny_server()
    server.pause()
    server.start()
    try:
        x = np.zeros((16, 16, 3), "float32")
        fut = server.submit(x)
        assert fut.get(timeout=60)["y"].shape == (4,)
    finally:
        server.stop()


def test_late_registration_starts_server():
    """A workload registered after start() gets its server running."""
    arb = ResourceArbiter(interval_s=0.01)
    arb.register("first", make_lut(), target_latency_ms=40.0, priority=1)
    arb.start(lambda: GlobalConstraints(total_chips=256))
    try:
        s = tiny_server()
        arb.register("late", make_lut(), target_latency_ms=40.0,
                     server=s)
        assert s.is_running
        x = np.zeros((16, 16, 3), "float32")
        fut = s.submit(x)
        assert fut.get(timeout=60)["y"].shape == (4,)
    finally:
        arb.stop()
