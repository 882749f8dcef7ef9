"""The readers of the program's own instrumentation (ISSUE 27): the span
ring put on a trace's clock, operations named by their jax.named_scope, and
the kernels by the name their pallas_call gives them. Driven on the trace
recorded on the chip (PR 25's fixture: no scopes of this PR in it, but real
op_name paths, a real profile_start_time and real kernels) and on synthetic
spans and device intervals made by hand."""
import importlib
import json
import os

import pytest

from perfbench import arith, families, harness as hs, trace
from perfbench.reducers import _program
from paddle_tpu.observability.spans import Span

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "train_tiny.xplane.pb")
STEP = "jit_step_fn(11331101634099302145)"


def reader(name):
    return importlib.import_module(f"perfbench.reducers.{name}")


def metric(name):
    spec = hs.load_json("metrics", name + ".json")
    return reader(spec["reducer"]), spec.get("args", {})


@pytest.fixture(scope="module")
def tr():
    return trace.Trace(FIXTURE)


@pytest.fixture()
def env(tr):
    return {"trace": tr, "busy": tr.busy_seconds(), "xplane_path": FIXTURE,
            "record": {}, "cfg": {}, "traffic": {}, "peaks": None}


# ------------------------------------------------- the recorded TPU trace

def test_wire_reader_finds_the_start_time_and_the_op_names():
    from jax.profiler import ProfileData
    m = _program.read_xplane_meta(FIXTURE)
    env_plane = next(p for p in ProfileData.from_file(FIXTURE).planes
                     if p.name == "Task Environment")
    assert m["profile_start_ns"] == dict(env_plane.stats)[
        "profile_start_time"] == 1790772760547486691
    assert set(m["op_names"]) == {
        STEP, "jit_convert_element_type(15388027131515875373)"}
    names = m["op_names"][STEP]
    assert len(names) > 2000
    paths = [v for v in names.values() if v]
    assert any(v.startswith("jit(step_fn)/transpose(jvp())/while/body/"
                            "closed_call/checkpoint/rematted_computation/")
               for v in paths)
    # a fusion with no metadata of its own takes its computation's
    assert sum(v is None for v in names.values()) < 0.45 * len(names)


def test_every_operation_gets_its_program_and_the_self_times_add_up(env, tr):
    rows = _program.op_rows(env)
    assert sum(r[0] for r in rows) == pytest.approx(tr.busy_seconds()[0],
                                                    rel=1e-6)
    assert {r[1] for r in rows} == {STEP}   # every operation has a program
    named = sum(r[0] for r in rows if r[3])
    assert named > 0.85 * sum(r[0] for r in rows)
    assert rows == sorted(rows, key=lambda r: -r[0])


def test_device_share_by_scope_on_the_fixture(env, capsys):
    share = reader("device_share_scope")
    scopes = ["checkpoint", "jit(take_along_axis)", "no_such_scope"]
    got = {s: share.read(env, scope=s, scopes=scopes) for s in scopes}
    # the remat'd backward of the layer scan is most of this tiny step
    assert 40 < got["checkpoint"] < 70
    assert 0 < got["jit(take_along_axis)"] < 10
    assert got["no_such_scope"] is None
    printed = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    inside = [p for p in printed if "device_share_inside" in p]
    printed = [p for p in printed if "device_share_by_scope" in p]
    assert len(printed) == 1            # the split is printed once a run
    assert [p["device_share_inside"] for p in inside] == scopes[:2]
    assert sum(inside[0]["by_primitive"].values()) <= got["checkpoint"] + 1e-9
    split = printed[0]["device_share_by_scope"]
    assert sum(d["total"] for d in split.values()) == pytest.approx(100)
    ck = split["checkpoint"]
    assert ck["recompute"] > 0 and ck["backward"] > 0
    assert ck["recompute"] + ck["backward"] == pytest.approx(ck["total"])
    assert split["(none)"]["total"] > 0
    assert len(printed[0]["largest_ops"]) == 8


@pytest.mark.parametrize("pat,scope,hit", [
    ("attn", "jit(f)/jvp()/while/body/closed_call/attn/dot_general", True),
    ("attn", "jit(f)/transpose(jvp(attn))/mul", True),
    ("attn", "jit(f)/jvp(attn)", True),
    ("attn", "jit(f)/attn_out/dot_general", False),
    ("mlp", "jit(f)/burst/while/body/mlp/jit(silu)/mul", True),
    ("head_loss", "jit(f)/jvp(head_loss)/jit(log_softmax)/sub", True),
    ("kv_read", "jit(f)/burst/while/body/kv_read/jit(_take)/gather", True),
    ("kv_read", "jit(f)/burst/while/body/kv_write/dynamic_update_slice",
     False)])
def test_scope_pattern(pat, scope, hit):
    assert bool(_program.scope_pattern(pat).search(scope)) is hit


def test_device_share_by_program_on_the_fixture(env):
    share = reader("device_share_program")
    assert share.read(env, program=r"^jit_step_fn\(") > 95
    # that program ran in the window, but none of its operations did
    assert share.read(env, program=r"^jit_convert_element_type\(") is None
    assert share.read(env, program="^jit_llama_paged_prefill_slot") is None


def test_flash_kernel_roofline_on_the_fixture(env, tr, capsys):
    rd = reader("flash_kernel_roofline")
    env["peaks"] = arith.load_peaks("TPU v5 lite")
    env["cfg"] = {"family": "llama", "hidden_size": 512,
                  "num_attention_heads": 4,
                  "num_key_value_heads": 4, "num_hidden_layers": 2,
                  "intermediate_size": 1024, "vocab_size": 512}
    env["record"] = {"batch": 2, "chips": 1, "seq_len": 128,
                     "step_t": [0.0] * 12}
    pat = 'custom_call_target="tpu_custom_call"'    # the fixture's kernels
    secs, calls = tr.matching_seconds(pat, "jit_step_fn")
    fwd = rd.read(env, kernels=pat, cost="fwd", within="jit_step_fn")
    bwd = rd.read(env, kernels=pat, cost="bwd", within="jit_step_fn")
    d = families.of(env["cfg"]).dims(env["cfg"])
    shape = (2, d["H"], d["KV"], 128, d["hd"])
    f1, b1 = arith.flash_fwd_cost(*shape)
    least, _ = arith.roofline_seconds(24 * f1, 24 * b1, env["peaks"])
    assert fwd == pytest.approx(100 * least / secs)
    assert 0 < fwd < bwd < 100
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert said[0]["kernel_calls"] == calls == 96
    assert said[0]["credited_calls"] == 24
    assert rd.read(env, kernels="^%flash_fwd", cost="fwd") is None
    # the names this PR gives the kernels, as the trace writes them
    fwd_rx, _ = (metric("kernel.flash_fwd_roofline.train")[1]["kernels"],
                 None)
    bwd_rx = metric("kernel.flash_bwd_roofline.train")[1]["kernels"]
    import re
    assert re.search(fwd_rx, "%flash_fwd.22 = (f32[2,4,128,128]{3,2,1,0}")
    assert not re.search(fwd_rx, "%flash_bwd_dq.12 = f32[2,4,128,128]")
    assert re.search(bwd_rx, "%flash_bwd_dq.12 = f32[2,4,128,128]")
    assert re.search(bwd_rx, "%flash_bwd_dkv = (f32[2,4,128,128]")
    assert not re.search(bwd_rx, "%flash_fwd.3 = f32[2]")


# ------------------------------- traces recorded with this PR's names
# PR 27, on the chip: `perfbench.run --rehearse --trace 1 --seconds 0.04
# --keep-trace` of both cells (tiny sizes on a TPU v5 lite): the kernels'
# names, the scopes and the program's own annotations as a trace has them.

NAMED = {"train": os.path.join(os.path.dirname(__file__), "fixtures",
                               "train_tiny_named.xplane.pb"),
         "serve": os.path.join(os.path.dirname(__file__), "fixtures",
                               "serve_tiny_named.xplane.pb")}


def named_env(kind):
    tr = trace.Trace(NAMED[kind])
    return {"trace": tr, "busy": tr.busy_seconds(), "record": {}, "cfg": {},
            "xplane_path": NAMED[kind], "peaks": None, "traffic": {}}


def host_annotations(path):
    from jax.profiler import ProfileData
    names: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    base = e.name.split("#")[0]
                    names[base] = names.get(base, 0) + 1
    return names


def test_train_scopes_and_kernel_names_as_the_chip_records_them(capsys):
    env = named_env("train")
    got = {}
    for scope in ("mlp", "attn", "head_loss", "optimizer"):
        rd, args = metric(f"train.device_share.{scope}")
        got[scope] = rd.read(env, **args)
    # the line that run printed on the chip (chiprun_out/p27/fs_*.out)
    assert got == pytest.approx({"mlp": 5.9235, "attn": 74.0815,
                                 "head_loss": 6.6165, "optimizer": 1.5525},
                                rel=1e-3)
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    split = said[0]["device_share_by_scope"]
    assert sum(d["total"] for d in split.values()) == pytest.approx(100)
    assert split["attn"]["recompute"] > 20        # remat's second forward
    assert split["optimizer"]["backward"] == 0
    assert said[0]["largest_ops"][0][:2] == [
        "flash_fwd.22",
        "jit(step_fn)/jvp()/while/body/closed_call/attn/flash_fwd/pallas_call"]
    # the kernels by the names their pallas_call gives them
    with open(os.path.join(hs.HERE, "configs", "rehearse",
                           "mistral-7b.l4.json")) as f:
        env["cfg"] = json.load(f)
    steps = sum(n.startswith("jit_step_fn(")
                for _, _, n in env["trace"].modules[0])
    env["record"] = {"batch": 2, "chips": 1, "seq_len": 128,
                     "step_t": [0.0] * steps}
    env["peaks"] = arith.load_peaks("TPU v5 lite")
    for name, per_layer in (("kernel.flash_fwd_roofline.train", 2),
                            ("kernel.flash_bwd_roofline.train", 2)):
        rd, args = metric(name)
        assert 0 < rd.read(env, **args) < 100
        line = json.loads(capsys.readouterr().out.splitlines()[-1])
        layers = env["cfg"]["num_hidden_layers"]
        assert line["credited_calls"] == steps * layers
        # forward: once and once more under remat; backward: dq and dk/dv
        assert line["kernel_calls"] == per_layer * line["credited_calls"]
    # the program's spans are in the trace, beside the harness's
    host = host_annotations(NAMED["train"])
    assert host["train.step"] == host["perfbench.train_step"] == steps
    assert host["loader.next"] == host["perfbench.next_batch"] == steps


def test_serving_scopes_and_programs_as_the_chip_records_them(capsys):
    env = named_env("serve")
    rd, args = metric("serve.device_share.kv_read")
    assert rd.read(env, **args) == pytest.approx(14.653, rel=1e-3)
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    split = said[0]["device_share_by_scope"]
    assert set(split) == set(args["scopes"]) | {"(none)"}
    assert all(split[s]["total"] > 0 for s in args["scopes"])
    assert "gather" in said[1]["by_primitive"]
    rd, args = metric("serve.device_share.prefill")
    assert rd.read(env, **args) == pytest.approx(20.157, rel=1e-3)
    host = host_annotations(NAMED["serve"])
    assert host["serve.step"] == host["perfbench.eng_step"] == 5
    for phase in ("serve.dispatch_burst", "serve.admit", "serve.readback",
                  "serve.merge"):
        assert host[phase] == 5, phase


# ------------------------------------------------------ synthetic spans

START = 1_790_000_000_000_000_000       # profile_start_time, wall ns


class FakeTrace:
    """A window of 1 s that opens 2 s into the trace; one chip, busy but
    for three gaps."""
    ops = {0: [(2.000, 2.100, "%a"), (2.110, 2.400, "%b"),
               (2.404, 2.900, "%c"), (2.901, 2.999, "%d")]}
    modules = {0: []}

    def window(self):
        return (2.0, 3.0)

    def busy(self, chip, lo, hi):
        return trace.clip(trace.union((s, e) for s, e, _ in self.ops[chip]),
                          lo, hi)


def sp(seq, name, t0, t1, parent=0, args=None, sid=None):
    return Span(seq, name, "serve", START + int(t0 * 1e9),
                START + int(t1 * 1e9), 1, sid or seq, parent, args)


RING = [
    sp(1, "import.paddle_tpu", 0.1, 0.9),
    sp(2, "compile.trace", 1.00, 1.50, args={"fun": "burst"}),
    sp(3, "compile.lower", 1.40, 1.60),          # overlaps the trace: once
    sp(4, "compile.backend", 1.60, 1.80, args={"fun": "jit(burst)"}),
    # step 1: dispatch (with a compile inside it), admit, readback, merge
    sp(5, "compile.backend", 2.020, 2.030, parent=10),
    sp(6, "serve.dispatch_burst", 2.000, 2.040, parent=20, sid=10),
    sp(7, "serve.admit", 2.040, 2.050, parent=20,
       args={"prefills": 1, "real": 600, "padded": 1024}),
    sp(8, "serve.readback", 2.050, 2.399, parent=20),
    sp(9, "serve.merge", 2.399, 2.405, parent=20),
    sp(10, "serve.step", 2.000, 2.405, sid=20),
    # step 2
    sp(11, "serve.dispatch_burst", 2.405, 2.410, parent=30),
    sp(12, "serve.admit", 2.410, 2.411, parent=30,
       args={"prefills": 0, "real": 0, "padded": 0}),
    sp(13, "serve.readback", 2.411, 2.8995, parent=30),
    sp(14, "serve.merge", 2.8995, 2.9005, parent=30),
    sp(15, "serve.step", 2.405, 2.9005, sid=30),
    # a step that began in the window and ended after it
    sp(16, "serve.readback", 2.95, 3.20, parent=40),
    sp(17, "serve.step", 2.9005, 3.20, sid=40),
    sp(18, "compile.backend", 3.5, 3.6),         # the reference, afterwards
]


@pytest.fixture()
def synth(monkeypatch):
    monkeypatch.setattr(_program, "program_records", lambda: list(RING))
    tr = FakeTrace()
    busy = trace.total(tr.busy(0, 2.0, 3.0))
    return {"trace": tr, "busy": (busy, 1.0), "record": {},
            "_xplane_meta": {"profile_start_ns": START, "op_names": {}}}


def test_self_time_per_step(synth):
    dispatch, args = metric("engine.dispatch_ms_per_step")
    # (40 - 10 ms of compile inside it) + 5 ms, over the 3 steps begun
    assert dispatch.read(synth, **args) == pytest.approx(35 / 3)
    merge, args = metric("engine.merge_ms_per_step")
    assert merge.read(synth, **args) == pytest.approx(7 / 3)
    admit, args = metric("engine.admit_ms_per_step")
    assert admit.read(synth, **args) == pytest.approx(11 / 3)
    train, args = metric("train.dispatch_ms_per_step")
    assert train.read(synth, **args) is None     # no train.step in the ring


def test_share_of_the_window_is_clipped_to_it(synth):
    rb, args = metric("engine.readback_wait_share")
    # 349 + 488.5 ms, and the 50 ms of the last readback inside the window
    assert rb.read(synth, **args) == pytest.approx(88.75)
    loader, args = metric("loader.wait_share")
    assert loader.read(synth, **args) is None


def test_idle_gaps_go_to_the_innermost_program_span(synth, capsys):
    ex, args = metric("engine.exposed_host_ms_per_step")
    value = ex.read(synth, **args)
    said = json.loads(capsys.readouterr().out.splitlines()[-1])
    gaps = said["exposed_host_s_by_span"]
    # whole gaps by their middle: 2.100-2.110 under the readback;
    # 2.400-2.404 under the merge (middle 2.402); 2.900-2.901 under step
    # 2's merge; 2.999-3.0 under the last readback
    assert gaps["serve.readback"] == pytest.approx(0.011)
    assert gaps["serve.merge"] == pytest.approx(0.005)
    assert set(gaps) == {"serve.readback", "serve.merge"}
    assert said["steps"] == 3 and value == pytest.approx(16 / 3)
    assert sum(gaps.values()) == pytest.approx(1.0 - synth["busy"][0])
    # along each gap: 2.400-2.404 is 4 ms of merge; 2.900-2.901 is half a
    # millisecond of merge and half of the next step outside its children
    along = said["exposed_host_s_along_gaps"]
    assert along["serve.readback"] == pytest.approx(0.011)
    assert along["serve.merge"] == pytest.approx(0.0045)
    assert along["serve.step"] == pytest.approx(0.0005)
    assert sum(along.values()) == pytest.approx(sum(gaps.values()))


def test_pad_share_counts_the_windows_admits(synth, capsys):
    pad, _ = metric("engine.pad_token_share")
    assert pad.read(synth) == pytest.approx(100 * (1 - 600 / 1024))
    assert json.loads(capsys.readouterr().out)["prefill_tokens"] == {
        "real": 600, "padded": 1024, "prefills": 1}


def test_setup_phases_are_what_ended_before_the_window(synth, capsys):
    imp, args = metric("setup.import_s")
    assert imp.read(synth, **args) == pytest.approx(0.8)
    tl, args = metric("setup.trace_lower_s")
    assert tl.read(synth, **args) == pytest.approx(0.6)     # a union
    be, args = metric("setup.compile_or_fetch_s")
    assert be.read(synth, **args) == pytest.approx(0.2)     # not 2.02, 3.5
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert said[1]["longest"][0][:2] == ["compile.trace", "burst"]


@pytest.mark.parametrize("name", [m["name"] for m in json.load(open(
    os.path.join(hs.ROOT, "BENCHMARK.json")))["per_layer"]
    if m["source"] in ("program_span", "program_counter")
    and m["name"] != "engine.slot_occupancy"])
def test_a_program_without_the_span_api_reports_nothing(name, synth,
                                                        monkeypatch):
    """The parent commit under this PR's benchmark files: the reader finds
    no public read, returns None and does not raise."""
    monkeypatch.setattr(_program, "program_records", lambda: None)
    rd, args = metric(name)
    assert rd.read(synth, **args) is None


def test_without_a_device_plane_or_a_start_time_nothing_is_reported(synth):
    rd, args = metric("engine.merge_ms_per_step")
    synth["_xplane_meta"] = {"profile_start_ns": None, "op_names": {}}
    assert rd.read(synth, **args) is None
    synth.pop("_program_spans")
    synth["_xplane_meta"] = {"profile_start_ns": START, "op_names": {}}
    synth["trace"].__class__ = type("NoDevice", (FakeTrace,), {"ops": {}})
    try:
        assert rd.read(synth, **args) is None
        scope, sargs = metric("serve.device_share.kv_read")
        assert scope.read(synth, **sargs) is None
    finally:
        synth["trace"].__class__ = FakeTrace


def test_program_records_is_the_rings_public_read():
    from paddle_tpu.observability import spans
    with spans.span("serve.step", cat="serve", burst=1, live=2):
        pass
    recs = _program.program_records()
    assert recs[-1].name == "serve.step" and recs[-1].t1_ns >= recs[-1].t0_ns


def test_the_runs_xplane_is_held_to_the_window_of_the_trace(tr):
    """The harness hands the readers the path of the run's own trace
    (`env["xplane_path"]`); a file whose `perfbench.window` annotation is
    not the window of `env["trace"]` gives nothing, never a shifted number,
    and neither does a run without a path."""
    assert _program.meta({"trace": tr}) is None
    assert _program.meta({"trace": None, "xplane_path": FIXTURE}) is None
    assert _program.meta({"trace": tr,
                          "xplane_path": NAMED["train"]}) is None
    got = _program.meta({"trace": tr, "xplane_path": FIXTURE})
    assert got["profile_start_ns"] == 1790772760547486691
    assert STEP in got["op_names"]
