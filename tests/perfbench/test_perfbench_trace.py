"""The reduction from a trace to numbers, on interval arithmetic by hand and
on a small trace recorded on the chip (PR 25: 12 steps of the rehearsal's
tiny train step on a TPU v5 lite, flash kernels included)."""
import os

import pytest

from perfbench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "train_tiny.xplane.pb")


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.total(trace.union([(0, 1), (0.5, 1.5), (3, 4)])) == 2.5
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_self_time_gives_a_loop_what_its_body_leaves():
    evs = [(0.0, 10.0, "while"), (1.0, 4.0, "body.a"), (4.0, 9.0, "body.b"),
           (5.0, 6.0, "inner"), (12.0, 13.0, "after")]
    got = trace.self_times(evs)
    assert got == pytest.approx({"while": 2.0, "body.a": 3.0, "body.b": 4.0,
                                 "inner": 1.0, "after": 1.0})
    assert sum(got.values()) == pytest.approx(11.0)   # the union's length


def test_short_name_keeps_name_opcode_and_shape():
    hlo = ('%closed_call.8 = (bf16[2,32,2048,128]{3,2,1,0:T(8,128)(2,1)S(1)}, '
           'f32[2,32,2048,1]{3,2,1,0}) custom-call(bf16[2,32,2048,128]{3,2,1,0}'
           ' %x), custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert trace.short_name(hlo) == \
        "%closed_call.8 tpu_custom_call bf16[2,32,2048,128]"
    assert trace.short_name("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), "
                            "kind=kLoop") == "%fusion.4 fusion f32[8]"
    assert trace.short_name("jit_step_fn(123)") == "jit_step_fn(123)"


@pytest.fixture(scope="module")
def tr():
    return trace.Trace(FIXTURE)


def test_fixture_window_busy_and_idle_share(tr):
    lo, hi = tr.window()
    assert hi - lo == pytest.approx(0.031160147, rel=1e-6)
    busy, window = tr.busy_seconds()
    assert window == pytest.approx(hi - lo)
    assert busy == pytest.approx(0.0012191760, rel=1e-5)
    assert 0 < busy < window
    assert 100 * (1 - busy / window) == pytest.approx(96.0874, abs=1e-3)


def test_fixture_per_module_time(tr):
    mods = tr.op_seconds("modules")
    step = [t for n, t in mods.items() if n.startswith("jit_step_fn(")]
    assert len(step) == 1 and step[0] == pytest.approx(0.001256, rel=2e-3)
    # the operations' self times add up to the busy union
    assert sum(tr.op_seconds().values()) == pytest.approx(
        tr.busy_seconds()[0], rel=1e-6)


def test_fixture_per_kernel_time(tr):
    pat = 'custom_call_target="tpu_custom_call"'
    secs, calls = tr.matching_seconds(pat, within="jit_step_fn")
    # 12 steps x 2 layers x (forward, remat's forward, dq, dk+dv)
    assert calls == 96
    assert 0 < secs < tr.busy_seconds()[0]
    assert tr.matching_seconds(pat, within="no_such_program") is None
    assert tr.matching_seconds("no_such_operation") is None
    top = trace.breakdown(tr)["device_ops"]
    assert len(top) == 10 and "tpu_custom_call" in top[0][0]
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))


def test_fixture_idle_gaps_go_to_the_harness_span_over_them(tr):
    gaps = tr.idle_gaps()
    busy, window = tr.busy_seconds()
    assert sum(gaps.values()) == pytest.approx(window - busy, rel=1e-6)
    assert set(gaps) <= {"perfbench.train_step", "perfbench.next_batch",
                         "outside_harness_spans"}
    assert max(gaps, key=gaps.get) == "perfbench.train_step"
    assert {n for _, _, n in tr.spans} == {
        "perfbench.window", "perfbench.train_step", "perfbench.next_batch"}


def test_a_trace_without_a_tpu_plane_gives_nothing(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("perfbench.window"):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    t = trace.Trace(trace.find_xplane(str(tmp_path)))
    assert t.busy_seconds() is None and t.op_seconds() == {}
    assert t.idle_gaps() == {} and t.window() is not None
