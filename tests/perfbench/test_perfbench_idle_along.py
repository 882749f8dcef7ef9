"""The device's idle milliseconds a step laid along the program's spans, on
the span clock fitted to the device's (`perfbench/reducers/
idle_along_spans.py`), and the dispatch's phases and the collector read as
milliseconds a step. Driven on synthetic spans and device intervals made by
hand: a window of 1 s, ten engine steps of 0.1 s, the device's clock
DELTA behind the spans', and prefill programs on the device between the
bursts, as in the hybrid cell."""
import importlib
import json

import pytest

from perfbench import harness as hs, trace
from perfbench.reducers import _program, idle_along_spans as ias, \
    span_total_ms_per_step as stm
from paddle_tpu.observability.spans import Span

START = 1_790_000_000_000_000_000       # profile_start_time, wall ns
DELTA = -0.0012                         # device time = span time + DELTA
BURST, PREFILL = "jit_llama_paged_decode_burst(7)", \
    "jit_llama_paged_prefill_slot(9)"
PHASES = ("serve.dispatch.prep", "serve.dispatch.put",
          "serve.dispatch.launch")
NEW = ["engine.dispatch_prep_ms_per_step", "engine.dispatch_put_ms_per_step",
       "engine.dispatch_launch_ms_per_step", "engine.gc_ms_per_step"]
IDLE = [f"device.idle_ms_per_step.{g}"
        for g in ("dispatch", "readback", "merge", "gc", "other")]


def metric(name):
    spec = hs.load_json("metrics", name + ".json")
    return (importlib.import_module(f"perfbench.reducers.{spec['reducer']}"),
            spec.get("args", {}))


def build(steps=range(-1, 10), burst=BURST, cross=False, phases=True):
    """(ring, device modules): step k begins at 2.01 + 0.1 k on the span
    clock; its burst starts 0.4 ms + k x 0.1 ms after its launch began and
    ends 0.1 ms + k x 0.02 ms before its readback does, or, in an even
    step, well before a prefill that the readback waits for."""
    ring, mods, seq = [], [], [0]

    def sp(name, t0, t1, parent=0, sid=None, args=None):
        seq[0] += 1
        ring.append(Span(seq[0], name, "serve", START + round(t0 * 1e9),
                         START + round(t1 * 1e9), 1, sid or 1000 + seq[0],
                         parent, args))

    for k in steps:
        t = 2.01 + 0.1 * k
        sid = 10 + k
        launch, rb_end = t + 0.0032, t + 0.090
        if phases:
            for name, a, b in zip(PHASES, (0.0002, 0.0012, 0.0032),
                                  (0.0012, 0.0032, 0.0058)):
                sp(name, t + a, t + b)
        sp("serve.dispatch_burst", t, t + 0.006, parent=sid)
        sp("serve.admit", t + 0.006, t + 0.009, parent=sid)
        sp("serve.readback", t + 0.009,
           t + 0.080 if cross and k == 3 else rb_end, parent=sid)
        sp("serve.merge", t + 0.090, t + 0.099, parent=sid)
        sp("serve.step", t, t + 0.099, sid=sid)
        if k == 4:      # a collection between two steps
            sp("gc.collect", t + 0.0993, t + 0.0999,
               args={"gen": 1, "collected": 0})
        b0 = launch + DELTA + 0.0004 + 0.0001 * max(k, 0)
        if k % 2 == 0:
            b1 = t + 0.060 + DELTA
            mods.append((t + 0.061 + DELTA, rb_end + DELTA - 0.0002, PREFILL))
        else:
            b1 = rb_end + DELTA - 0.0001 - 0.00002 * k
        mods.append((b0, b1, burst))
    sp("compile.backend", 3.5, 3.6)     # the reference, afterwards
    return ring, sorted(mods)


class FakeTrace:
    """One chip, busy exactly while a module runs; the window 2.0-3.0 s."""

    def __init__(self, mods):
        self.modules = {0: mods}
        self.ops = {0: [(s, e, "%op") for s, e, _ in mods]}

    def window(self):
        return (2.0, 3.0)

    def busy(self, chip, lo, hi):
        return trace.clip(trace.union((s, e) for s, e, _ in self.ops[chip]),
                          lo, hi)


def env_of(monkeypatch, ring, mods, counting=True):
    """`counting`: the program counts collections (the parent's does not)."""
    monkeypatch.setattr(_program, "program_records", lambda: list(ring))
    monkeypatch.setattr(stm, "program_counters",
                        lambda: {"gc.collections": 7} if counting else {})
    tr = FakeTrace(mods)
    return {"trace": tr, "busy": (trace.total(tr.busy(0, 2.0, 3.0)), 1.0),
            "record": {},
            "_xplane_meta": {"profile_start_ns": START, "op_names": {}}}


def said(capsys, key):
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    return next(x for x in lines if key in x)


def test_the_fit_finds_the_planted_offset_between_prefills(monkeypatch,
                                                           capsys):
    ring, mods = build()
    env = env_of(monkeypatch, ring, mods)
    rd, args = metric("device.idle_ms_per_step.dispatch")
    assert rd.read(env, **args) is not None
    out = said(capsys, "span_clock_fit")
    fit = out["span_clock_fit"]
    # the step before the window has its burst on the trace but its launch
    # outside: the pairs begin one burst later, and prefills pair with none
    assert fit["pairs"] == 10 and out["steps"] == 10
    assert fit["lower"] <= DELTA <= fit["upper"]
    assert fit["upper"] == pytest.approx(DELTA + 0.0004)
    assert fit["lower"] == pytest.approx(DELTA - 0.0001 - 0.00002)
    assert fit["delta"] == pytest.approx(fit["shifted_s"])
    assert fit["delta"] == pytest.approx(fit["lower"])  # 0.12 ms under
    assert out["ring_holds_window"] is True
    # on the fitted clock the device waits through every prep and put,
    # and through the launch until its burst starts
    along = out["idle_s_along_fitted_spans"]
    assert along["serve.dispatch.prep"] == pytest.approx(0.010)
    assert along["serve.dispatch.put"] == pytest.approx(0.020)
    assert along["serve.dispatch.launch"] == pytest.approx(
        sum(0.0004 + 0.0001 * k + 0.00012 for k in range(10)))


def test_pairs_go_by_name_and_order():
    launches = [(1.0, 1.001), (2.0, 2.001)]
    readbacks = [(1.002, 1.5), (2.002, 2.5)]
    bursts = [(0.4, 0.45), (0.999, 1.3), (1.999, 2.3)]
    got = ias.pair_bursts(launches, readbacks, bursts)
    assert got == [(1.0, 1.5, 0.999, 1.3), (2.0, 2.5, 1.999, 2.3)]
    assert ias.pair_bursts([], readbacks, bursts) == []


@pytest.mark.parametrize("case", ["no_module", "crossed", "few"])
def test_where_nothing_can_be_fitted_nothing_is_shifted(case, monkeypatch,
                                                        capsys):
    ring, mods = build(burst="jit__unknown(7)" if case == "no_module"
                       else BURST, cross=case == "crossed",
                       steps=range(4) if case == "few" else range(-1, 10))
    env = env_of(monkeypatch, ring, mods)
    values = {n: metric(n)[0].read(env, **metric(n)[1]) for n in IDLE}
    out = said(capsys, "span_clock_fit")
    fit = out["span_clock_fit"]
    assert fit["delta"] is None and fit["shifted_s"] == 0.0
    assert {"no_module": "matches", "crossed": "cross",
            "few": "fewer"}[case] in fit["why"]
    assert all(v is not None for v in values.values())
    # the reading is still the whole idle time, on the unshifted spans
    idle = 1.0 - env["busy"][0]
    assert sum(values.values()) * out["steps"] / 1e3 == pytest.approx(idle)


def test_the_five_idle_metrics_sum_to_the_idle_time(monkeypatch, capsys):
    ring, mods = build()
    env = env_of(monkeypatch, ring, mods)
    values = {n: metric(n)[0].read(env, **metric(n)[1]) for n in IDLE}
    steps = said(capsys, "span_clock_fit")["steps"]
    idle = 1.0 - env["busy"][0]
    assert sum(values.values()) * steps / 1e3 == pytest.approx(idle)
    assert all(v >= 0 for v in values.values())
    # the collection between steps 4 and 5 lay in an idle gap
    assert values["device.idle_ms_per_step.gc"] == pytest.approx(0.06)
    assert values["device.idle_ms_per_step.readback"] > 0
    assert values["device.idle_ms_per_step.merge"] == pytest.approx(9.0)


def test_phases_and_the_collector_in_ms_per_step(monkeypatch):
    ring, mods = build()
    env = env_of(monkeypatch, ring, mods)
    got = {n: metric(n)[0].read(env, **metric(n)[1]) for n in NEW}
    assert got["engine.dispatch_prep_ms_per_step"] == pytest.approx(1.0)
    assert got["engine.dispatch_put_ms_per_step"] == pytest.approx(2.0)
    assert got["engine.dispatch_launch_ms_per_step"] == pytest.approx(2.6)
    assert got["engine.gc_ms_per_step"] == pytest.approx(0.06)
    # overlays: the dispatch's self time is its whole 6 ms
    rd, args = metric("engine.dispatch_ms_per_step")
    assert rd.read(env, **args) == pytest.approx(6.0)
    # a window in which no collection of generation 1 or 2 came reads 0
    env = env_of(monkeypatch, [r for r in ring if r.name != "gc.collect"],
                 mods)
    rd, args = metric("engine.gc_ms_per_step")
    assert rd.read(env, **args) == 0.0


@pytest.mark.parametrize("name", NEW + IDLE)
def test_every_new_reader_returns_none_where_it_has_nothing(name,
                                                            monkeypatch):
    rd, args = metric(name)
    assert rd.read({"trace": None, "busy": None, "record": {}},
                   **args) is None
    ring, mods = build()
    env = env_of(monkeypatch, ring, mods)
    monkeypatch.setattr(_program, "program_records", lambda: None)
    assert rd.read(env, **args) is None
    # the parent's program: the same steps, no phases and no collector
    ring, mods = build(phases=False)
    ring = [r for r in ring if r.name != "gc.collect"]
    env = env_of(monkeypatch, ring, mods, counting=False)
    assert rd.read(env, **args) is None
    env = env_of(monkeypatch, [], mods)
    assert rd.read(env, **args) is None


def test_a_reader_that_meets_a_fault_says_so_and_returns_none(monkeypatch,
                                                              capsys):
    ring, mods = build()
    env = env_of(monkeypatch, ring, mods)

    def broken(*a, **k):
        raise ValueError("a trace unlike any seen")

    monkeypatch.setattr(ias, "gaps_by_span", broken)
    rd, args = metric("device.idle_ms_per_step.merge")
    assert rd.read(env, **args) is None
    assert "a trace unlike any seen" in said(capsys, "idle_along_spans")[
        "idle_along_spans"]


def test_the_collector_reader_that_meets_a_fault_says_so(monkeypatch,
                                                         capsys):
    ring, mods = build()
    env = env_of(monkeypatch, ring, mods)

    def broken(env):
        raise ValueError("a ring unlike any seen")

    monkeypatch.setattr(_program, "program_spans", broken)
    rd, args = metric("engine.gc_ms_per_step")
    assert rd.read(env, **args) is None
    assert "a ring unlike any seen" in said(
        capsys, "span_total_ms_per_step")["span_total_ms_per_step"]
