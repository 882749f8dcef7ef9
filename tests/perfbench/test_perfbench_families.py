"""The model-family seam (ISSUE 29). That moving the Llama's shape behind
perfbench/families/llama.py moved nothing: the values below were printed by
the parent commit's perfbench/arith.py, weights.py and readers before the
code moved. And that the seam is whole: a second family made of files under
tests/perfbench/ alone (stub_family.py) is taken from set-up to a result's
line by a serving and a training mix, with nothing under perfbench/ patched.
"""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

import stub_family
from _drive import assert_line_shape, drive
from perfbench import arith, families, harness as hs
from perfbench.families import llama
from perfbench.reducers import (flash_kernel_roofline, serve_step_mfu,
                                serve_work_roofline, train_step_mfu)
from perfbench.weights import make_weights

CONFIGS = os.path.join(hs.HERE, "configs")
STEPS = [{"prefills": [700, 1536], "decode_steps": 8,
          "decodes": [(700, 7), (1536, 7), (1023, 8), (511, 3)]},
         {"prefills": [], "decode_steps": 8, "decodes": [(900, 8), (17, 8)]},
         {"prefills": [33], "decode_steps": 0, "decodes": []}]
TINY = dict(total_params=106816, matmul_params=90432, weight_bytes=180864,
            kv_bytes_per_token=256, prefill_flops_1000=403488768.0,
            decode_flops_500=436864.0, train_flops_2048=2115456.0,
            least_seconds=1.942990887755899e-05, window_flops=1088621952.0,
            L=2, D=64, F=128, V=256, H=4, KV=2, hd=16,
            digest="b4eb545a1ad15f458ba555c00bb0d36c"
                   "469044cd06b423e0d2091c0a4e7fc89e")
PARENT = {      # printed by the parent commit (1046287), not by this code
    "internlm2-1.8b.json": dict(
        total_params=1889110016, matmul_params=1699579904,
        weight_bytes=3399159808, kv_bytes_per_token=98304,
        prefill_flops_1000=3118581940224.0, decode_flops_500=3497463808.0,
        train_flops_2048=10801459200.0, least_seconds=0.11021355648392388,
        window_flops=7279321026560.0,
        L=24, D=2048, F=8192, V=92544, H=16, KV=8, hd=128, digest=None),
    "mistral-7b.l4.json": dict(
        total_params=1134596096, matmul_params=1003524096,
        weight_bytes=2007048192, kv_bytes_per_token=16384,
        prefill_flops_1000=1777860608000.0, decode_flops_500=2039816192.0,
        train_flops_2048=6222471168.0, least_seconds=0.06260034960441672,
        window_flops=4137651642368.0,
        L=4, D=4096, F=14336, V=32000, H=32, KV=8, hd=128, digest=None),
    "rehearse/internlm2-1.8b.json": TINY,
    "rehearse/mistral-7b.l4.json": TINY}


def config(rel):
    with open(os.path.join(CONFIGS, rel)) as f:
        return json.load(f)


# ------------------------------------------------- the move moved nothing

@pytest.mark.parametrize("rel", sorted(PARENT))
def test_counts_and_bytes_are_the_parents(rel):
    cfg, want = config(rel), PARENT[rel]
    assert families.of(cfg) is llama
    assert llama.total_params(cfg) == want["total_params"]
    assert llama.matmul_params(cfg) == want["matmul_params"]
    assert llama.weight_bytes(cfg) == want["weight_bytes"]
    assert llama.kv_bytes_per_token(cfg) == want["kv_bytes_per_token"]
    assert llama.held_bytes(cfg, 1000, 7) == 1000 * want["kv_bytes_per_token"]


@pytest.mark.parametrize("rel", sorted(PARENT))
def test_operations_at_fixed_lengths_are_the_parents(rel):
    cfg, want = config(rel), PARENT[rel]
    assert llama.prefill_flops(cfg, 1000) == want["prefill_flops_1000"]
    assert llama.decode_flops(cfg, 500) == want["decode_flops_500"]
    assert llama.train_flops_per_token(cfg, 2048) == want["train_flops_2048"]
    flops, byts = llama.prefill_work(cfg, 1000)
    assert flops == want["prefill_flops_1000"]
    assert byts == want["weight_bytes"] + 1000 * want["kv_bytes_per_token"]
    # a burst by step: the sum over its tokens, the weights once a step
    flops, byts = llama.burst_work(cfg, 8, [(499, 1), (9, 3)])
    assert flops == want["decode_flops_500"] + sum(
        llama.decode_flops(cfg, c) for c in (10, 11, 12))
    assert byts == 8 * want["weight_bytes"] + want["kv_bytes_per_token"] \
        * (500 + 1 + 10 + 11 + 12 + 3)


@pytest.mark.parametrize("rel", sorted(PARENT))
def test_readers_of_a_recorded_steps_list_read_the_parents_numbers(rel):
    cfg, want = config(rel), PARENT[rel]
    peaks = arith.load_peaks("TPU v5 lite")
    record = {"steps": STEPS, "chips": 1}
    assert serve_work_roofline.least_seconds(record, cfg, peaks) \
        == pytest.approx(want["least_seconds"], rel=1e-14)
    assert serve_step_mfu.window_flops(record, cfg) == want["window_flops"]
    env = {"busy": (0.2, 0.25), "peaks": peaks, "record": record, "cfg": cfg}
    assert serve_work_roofline.read(env) == pytest.approx(
        100 * want["least_seconds"] / 0.2, rel=1e-14)
    assert serve_step_mfu.read(env) == pytest.approx(
        100 * want["window_flops"] / (197e12 * 0.25), rel=1e-14)
    env["record"] = {"step_t": [0.0] * 10, "batch": 2, "seq_len": 2048,
                     "chips": 1}
    assert train_step_mfu.read(env) == pytest.approx(
        100 * 10 * 2 * 2048 * want["train_flops_2048"] / (197e12 * 0.25),
        rel=1e-14)


@pytest.mark.parametrize("rel", sorted(PARENT))
def test_attention_calls_of_a_train_step_are_one_a_layer(rel):
    cfg, want = config(rel), PARENT[rel]
    shape = (2, want["H"], want["KV"], 2048, want["hd"])
    assert llama.train_attention_calls(cfg, 2, 2048) == [(shape, want["L"])]
    env = {"cfg": cfg, "record": {"step_t": [0.0] * 10, "batch": 2,
                                  "seq_len": 2048, "chips": 1}}
    f1, b1 = arith.flash_fwd_cost(*shape)
    f2, b2 = arith.flash_bwd_cost(*shape)
    n = 10 * want["L"]  # the parent's: n * (f1 + f2), n * (b1 + b2)
    assert flash_kernel_roofline.credited(
        env, [arith.flash_fwd_cost, arith.flash_bwd_cost]) \
        == (n, n * (f1 + f2), n * (b1 + b2))
    assert flash_kernel_roofline.credited(env, [arith.flash_bwd_cost]) \
        == (n, n * f2, n * b2)


@pytest.mark.parametrize("rel", sorted(PARENT))
def test_parameter_tree_and_weights_are_the_parents(rel):
    cfg, want = config(rel), PARENT[rel]
    L, D, F, V, H, KV, hd = (want[k] for k in ("L", "D", "F", "V", "H", "KV",
                                               "hd"))
    assert list(llama.shapes(cfg).items()) == [
        ("embed_tokens", (V, D)), ("wq", (L, D, H * hd)),
        ("wk", (L, D, KV * hd)), ("wv", (L, D, KV * hd)),
        ("wo", (L, H * hd, D)), ("w_gate", (L, D, F)), ("w_up", (L, D, F)),
        ("w_down", (L, F, D)), ("lm_head", (D, V)), ("ln1", (L, D)),
        ("ln2", (L, D)), ("norm", (D,))]
    assert llama.GAINS == ("ln1", "ln2", "norm")
    assert [llama.layer_axes(k, len(s)) for k, s in llama.shapes(cfg).items()
            ] == [None] + [(1, 2)] * 7 + [None] + [(1,)] * 2 + [None]
    if want["digest"] is None:      # the published sizes are made on the chip
        return
    w, h = make_weights(cfg, 5), hashlib.sha256()
    for k in sorted(w):
        a = np.asarray(w[k])
        for part in (k, str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == want["digest"]


# ------------------------------------------------------- the look-up itself

def test_a_configuration_says_what_it_is():
    with pytest.raises(SystemExit, match='add "family" to its file'):
        families.of({"name": "nameless"})
    with pytest.raises(SystemExit,
                       match="add perfbench/families/no_such_family.py"):
        families.of({"name": "x", "family": "no_such_family"})
    assert families.of({"family": "llama"}).reference().__name__ \
        == "perfbench.ref.llama"


# ------------------------------------------------------- the seam is whole

@pytest.fixture()
def stub(monkeypatch):
    """The stub family under the name the look-up finds: registered, with
    no attribute of any module under perfbench/ replaced."""
    monkeypatch.setitem(sys.modules, "perfbench.families.stub", stub_family)
    del stub_family.HELD[:]

    def edit(ctx):
        ctx["cfg"] = dict(stub_family.CFG)
    return edit


def test_stub_family_serves_from_set_up_to_the_line(stub, capsys):
    bench = hs.load_cell("internlm2-1.8b.longctx-batch", True)["bench"]
    line = drive("internlm2-1.8b.longctx-batch", seconds=0.1, edit=stub)
    assert_line_shape(line, {m["name"] for m in bench["end_to_end"]})
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {"served_logit_gap_max", "failed_requests",
                                     "compilations_in_window"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["rehearsal.output_tokens_per_s"]["value"] > 0
    # the bytes held at the close are the family's: a state a live request
    # in three layers, a cache row a token in the fourth
    info = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith('{"window_s"')][0]
    (rows, n_live), = stub_family.HELD
    assert 0 <= n_live <= 4 and rows >= n_live    # short outputs: maybe none
    assert info["live_kv_bytes_at_close"] == n_live * 3 * 4096 + rows * 64


def test_stub_family_traced_serving_run_reads_its_counters(stub):
    line = drive("internlm2-1.8b.longctx-batch", seconds=0.1, trace=True,
                 edit=stub)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"rehearsal.engine.slot_occupancy"}


def test_stub_family_trains_from_set_up_to_the_line(stub):
    bench = hs.load_cell("mistral-7b.train-packed-2k", True)["bench"]
    line = drive("mistral-7b.train-packed-2k", edit=stub)
    assert_line_shape(line, {m["name"] for m in bench["end_to_end"]})
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {"loss_gap_step1", "grad_norm_gap",
                                     "change_norm_gap",
                                     "compilations_in_window"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["rehearsal.train_tokens_per_s"]["value"] > 0


def test_stub_family_fault_is_not_correct(stub):
    """The faults need nothing of the model but the configuration's dict."""
    line = drive("mistral-7b.train-packed-2k", fault="half_batch", edit=stub)
    assert line["correct"] is False
    assert not line["compared"]["grad_norm_gap"]["ok"]


def test_readers_take_a_fixed_state_and_a_layer_pattern_by_step(stub):
    """What the readers ask is by step, so a family whose state does not
    grow with the context and whose cache and kernel live in some layers
    only answers in its own file: worked here by hand for the stub."""
    cfg, peaks = dict(stub_family.CFG), arith.load_peaks("TPU v5 lite")
    w = 4 * (2 * 256 * 32 + 2 * 32)
    record = {"chips": 1, "steps": [
        {"prefills": [100], "decode_steps": 4, "decodes": [(100, 3), (9, 4)]}]}
    pf = 2.0 * 32 * 256 * 100 + 2.0 * 32 * 100 * 100
    pb = w + 3 * 4096 + 64 * 100
    rows = (101 + 102 + 103 + 3) + (10 + 11 + 12 + 13 + 4)
    bf = 2.0 * 32 * 256 * 7 + 4.0 * 32 * (rows - 7)
    bb = 4 * w + 7 * 2 * 3 * 4096 + rows * 64
    assert stub_family.prefill_work(cfg, 100) == (pf, pb)
    assert stub_family.burst_work(cfg, 4, [(100, 3), (9, 4)]) == (bf, bb)
    assert serve_work_roofline.least_seconds(record, cfg, peaks) \
        == pytest.approx(arith.roofline_seconds(pf, pb, peaks)[0]
                         + arith.roofline_seconds(bf, bb, peaks)[0])
    assert serve_step_mfu.window_flops(record, cfg) == pf + bf
    # the attention kernel runs in the one `attn` layer of four
    env = {"cfg": cfg, "record": {"step_t": [0.0] * 5, "batch": 2,
                                  "seq_len": 128, "chips": 1}}
    f, b = arith.flash_fwd_cost(2, 4, 4, 128, 8)
    assert flash_kernel_roofline.credited(env, [arith.flash_fwd_cost]) \
        == (5, 5 * f, 5 * b)
