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


# ------------------------------------------------ a trace that is cut short
# PR 28: with 25 230 device operations a burst the profiler's buffer was full
# after ~28 s of a 40 s window; busy time was cut there, and the roofline and
# idle shares read 94.9 % and 32.5 %, both wrong (PERF.md section 6).

def cut_in_the_middle(t):
    """The fixture as a full buffer would have left it: no device event
    past the middle of the window."""
    lo, hi = t.window()
    t.ops = {c: [ev for ev in evs if ev[1] <= (lo + hi) / 2]
             for c, evs in t.ops.items()}
    t._busy = {}
    return t


def test_a_whole_trace_is_not_cut_and_a_cut_one_says_by_how_much(tr):
    assert tr.cut_short() is None
    cut = cut_in_the_middle(trace.Trace(FIXTURE))
    lo, hi = cut.window()
    tail = cut.cut_short()
    assert (hi - lo) / 2 <= tail < 0.6 * (hi - lo)
    assert tail == pytest.approx(hi - max(e for _, e, _ in cut.ops[0]))
    # a device that idles at the window's end as long as it did before is
    # not cut: an open loop waits for its next arrival
    lone = trace.Trace(FIXTURE)
    lone.ops = {0: [(lo + 0.30 * (hi - lo), lo + 0.31 * (hi - lo), "%a"),
                    (lo + 0.60 * (hi - lo), lo + 0.61 * (hi - lo), "%b")]}
    lone._busy = {}
    assert lone.cut_short() is None


@pytest.mark.parametrize("whole,keep", [(True, False), (False, False),
                                        (True, True)],
                         ids=["whole", "cut", "kept"])
def test_a_cut_trace_leaves_the_device_trace_metrics_out(
        whole, keep, tmp_path, monkeypatch, capsys):
    import shutil

    import perfbench.run as prun
    cell = "mistral-7b.train-packed-2k"
    ctx = prun.context(cell, rehearse=True, trace=True)
    ctx["rehearse"] = False         # the peaks of the chip that recorded it
    ctx["keep_trace"] = str(tmp_path / "kept") if keep else None
    prof = tmp_path / "perfbench_trace_x" / "plugins" / "profile" / "t0"
    prof.mkdir(parents=True)
    shutil.copy(FIXTURE, prof / "host.xplane.pb")
    if not whole:
        read = trace.Trace
        monkeypatch.setattr(trace, "Trace",
                            lambda path: cut_in_the_middle(read(path)))
    result = {"trace_dir": str(tmp_path / "perfbench_trace_x"),
              "record": {"step_t": [0.0] * 12, "batch": 2, "seq_len": 128,
                         "chips": 1, "data_wait_s": 0.001, "window_s": 0.031}}
    metrics, extra, brk = prun.per_layer(
        ctx, result, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    sources = {m["name"]: m["source"] for m in ctx["bench"]["per_layer"]}
    said = capsys.readouterr().out
    assert "train.data_wait_share" in metrics       # the host clock's
    assert extra["busy_s"] > 0 and brk["device_ops"]
    if whole:
        assert "trace_cut_short" not in said
        assert {"train.step_mfu", "device.idle_share.train",
                "kernel.flash_roofline.train"} <= set(metrics)
    else:
        assert '{"trace_cut_short": "the last device event ends 0.01' in said
        assert not [n for n in metrics if sources[n] == "device_trace"]
    # the profiler's directory goes once the readers have run, unless the
    # trace is kept
    assert os.path.exists(result["trace_dir"]) == keep
    assert os.path.exists(tmp_path / "kept" / "host.xplane.pb") == keep
