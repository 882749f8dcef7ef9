"""The Olmo-Hybrid family under perfbench/: its counts to the digit, the
work by step at fixed lengths, the reference (pattern in `hashable`, the
period's runs), the cell's rehearsal from set-up to the line with the int8
control and the planted fault coming out not correct, and the scope-roofline
reader on a recorded trace."""
import json
import os
import sys
import types

import numpy as np
import pytest

from _drive import assert_line_shape, drive
from perfbench import arith, families, harness as hs
from perfbench.families import olmo_hybrid as fam
from perfbench.reducers import (scope_roofline, serve_step_mfu,
                                serve_work_roofline)

CELL = "olmo-hybrid-7b.longdoc-batch"
CONFIGS = os.path.join(hs.HERE, "configs")


def config(rel="olmo-hybrid-7b.l16.json"):
    with open(os.path.join(CONFIGS, rel)) as f:
        return json.load(f)


# ------------------------------------------------------ counts, to the digit

def test_parameters_cache_and_state_are_issue_30s():
    cfg = config()
    assert families.of(cfg) is fam
    assert fam.full_layer_params(cfg) == 185_809_920
    assert fam.linear_layer_params(cfg) == 215_570_172
    period = 3 * fam.linear_layer_params(cfg) + fam.full_layer_params(cfg)
    assert period == 832_520_436
    assert fam.total_params(cfg) == 4 * period + 770_703_360 + 3_840 \
        == 4_100_788_944
    assert fam.matmul_params(cfg) == 4_100_788_944 - 100_352 * 3_840
    assert fam.weight_bytes(cfg) == 7_430_874_528
    assert fam.kv_bytes_per_token(cfg) == 61_440
    assert fam.rule_state_bytes(cfg) == 30 * 192 * 96 * 4 == 2_211_840
    assert fam.state_bytes(cfg) == 12 * (552_960 * 4 + 34_560 * 2) \
        == 27_371_520
    assert fam.held_bytes(cfg, 1000, 7) == 1000 * 61_440 + 7 * 27_371_520
    # the published model: 32 layers of the same pattern
    whole = dict(cfg, num_hidden_layers=32,
                 layer_types=cfg["published"]["layer_types"])
    assert fam.total_params(whole) == 8 * period + 770_703_360 + 3_840
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:16]


def test_the_file_holds_the_catalogs_numbers():
    """Every number of the published config under the same key, but the
    two that `reduced` names."""
    cfg = config()
    want = {"vocab_size": 100352, "hidden_size": 3840,
            "intermediate_size": 11008, "num_attention_heads": 30,
            "num_key_value_heads": 30, "max_position_embeddings": 65536,
            "rms_norm_eps": 1e-06, "linear_num_key_heads": 30,
            "linear_num_value_heads": 30, "linear_key_head_dim": 96,
            "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4}
    assert {k: cfg[k] for k in want} == want
    assert cfg["rope_parameters"] == {"rope_theta": None}
    assert cfg["linear_allow_neg_eigval"] is True
    assert cfg["tie_word_embeddings"] is False
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["state_dtype"] == "float32" and "pipeline" in cfg["deployment"]
    assert {"head_dim", "rotation", "wiring", "rule", "state_dtype",
            "values", "weights"} <= set(cfg["assumed"])


def test_parameter_tree_is_the_programs_each_kind_on_its_own_axis():
    cfg = config()
    shp = fam.shapes(cfg)
    assert shp["wq"] == (4, 3840, 3840) and shp["q_norm"] == (4, 3840)
    assert shp["lin_wqkv"] == (12, 3840, 11520)
    assert shp["lin_conv"] == (12, 4, 11520) and shp["lin_norm"] == (12, 192)
    assert shp["w_gate"] == (16, 3840, 11008) and shp["ln1"] == (16, 3840)
    assert sum(int(np.prod(s)) for s in shp.values()) == 4_100_788_944
    assert fam.layer_axes("lin_A_log", 2) == (1,)
    assert fam.layer_axes("lin_wqkv", 3) == (1, 2)
    assert fam.layer_axes("lm_head", 2) is None
    # the program's own tree has the same leaves and shapes
    from paddle_tpu.inference.replica import _spec_config
    from paddle_tpu.models.llama import llama_init_params
    import jax
    tiny = config("rehearse/olmo-hybrid-7b.l16.json")
    spec = _spec_config({"config": fam.model_spec(tiny, 64)})
    tree = jax.eval_shape(lambda: llama_init_params(spec))
    assert {k: v.shape for k, v in tree.items()} == fam.shapes(tiny)
    assert spec.state_bytes_per_request() == fam.state_bytes(tiny, 4)
    # the state's precision is the file's: no limit of the chip comparison
    # holds it (PERF.md section 2: a bfloat16 state reads as the program)
    full = _spec_config({"config": fam.model_spec(cfg, 3456)})
    assert full.state_shapes(24)["state"] == ((24, 30, 192, 96), np.float32)


# --------------------------------------------------------- the work by step

def test_work_by_step_at_fixed_lengths():
    cfg = config()
    per_tok = 2.0 * (fam.matmul_params(cfg) - 3840 * 100352 - 3840
                     - 4 * 2 * 3840 - 12 * (60 + 192) - 32 * 3840)
    chunk = 2.0 * 64 * 64 * (3 * 96 + 2 * 192) + 2.0 * 64 ** 3 / 3 \
        + 6.0 * 64 * 96 * 192
    scan = 12 * 30 * 32 * chunk                   # 2000 tokens: 32 chunks
    assert fam.scan_flops(cfg, 2000) == scan
    flops, byts = fam.prefill_work(cfg, 2000)
    assert flops == per_tok * 2000 + 2.0 * 3840 * 100352 \
        + 2.0 * 4 * 30 * 128 * 2000 * 2000 + scan
    assert byts == 7_430_874_528 + 2000 * 61_440 + 27_371_520
    # a burst: weights once a step, live rows, the state twice a token
    step = 7.0 * 12 * 30 * 192 * 96
    assert fam.step_flops(cfg) == step
    flops, byts = fam.burst_work(cfg, 8, [(499, 1), (9, 3)])
    assert flops == pytest.approx(
        sum(fam.decode_flops(cfg, c) for c in (500, 10, 11, 12)), rel=1e-14)
    assert fam.decode_flops(cfg, 500) == 2.0 * fam.matmul_params(cfg) \
        + 4.0 * 4 * 30 * 128 * 500 + step
    assert byts == 8 * 7_430_874_528 + 61_440 * (500 + 1 + 10 + 11 + 12 + 3) \
        + 4 * 2 * 27_371_520
    # the scopes: the scans of the prefills, the rule of the decoded tokens
    steps = [{"prefills": [2000, 100], "decode_steps": 8,
              "decodes": [(499, 1), (9, 3)]},
             {"prefills": [], "decode_steps": 8, "decodes": [(30, 8)]}]
    assert fam.scope_work(cfg, "gdn_scan", steps) == (
        scan + 12 * 30 * 2 * chunk,
        fam.scan_bytes(cfg, 2000) + fam.scan_bytes(cfg, 100))
    assert fam.scan_bytes(cfg, 100) == 12 * (
        100 * ((11520 + 5760) * 2 + 2 * 30 * 4) + 2_211_840)
    assert fam.scope_work(cfg, "gdn_step", steps) == (
        12 * step, 12.0 * 2 * 12 * 2_211_840)
    assert fam.scope_work(cfg, "mlp", steps) is None
    # the readers that ask by step take it as it stands
    peaks = arith.load_peaks("TPU v5 lite")
    record = {"steps": steps, "chips": 1}
    assert serve_step_mfu.window_flops(record, cfg) == pytest.approx(sum(
        fam.prefill_work(cfg, t)[0] for t in (2000, 100)) + sum(
        fam.burst_work(cfg, 8, s["decodes"])[0] for s in steps), rel=1e-14)
    assert serve_work_roofline.least_seconds(record, cfg, peaks) > 0
    # a decode step is bound by bytes: weights, then state, then KV
    f, b = fam.burst_work(cfg, 1, [(2000, 1)] * 24)
    assert arith.roofline_seconds(f, b, peaks)[1] == "memory"


def test_training_is_arithmetic_only():
    cfg = config()
    assert fam.train_attention_calls(cfg, 2, 2048) == [
        ((2, 30, 30, 2048, 128), 4)]
    assert fam.train_flops_per_token(cfg, 2048) == \
        6.0 * fam.matmul_params(cfg) + 6.0 * 4 * 30 * 128 * 2048 \
        + 3.0 * fam.scan_flops(cfg, 2048) / 2048
    with pytest.raises(SystemExit, match="no training cell"):
        fam.train_step(cfg, {}, None, None)


# ------------------------------------------------------------ the reference

def test_reference_carries_the_pattern_and_imports_nothing_of_the_program():
    ref = fam.reference()
    assert ref.__name__ == "perfbench.ref.olmo_hybrid"
    h = dict(ref.hashable(config()))
    assert h["pattern"] == ("linear_attention",) * 3 + ("full_attention",) \
        + tuple(config()["layer_types"][4:])
    assert (h["H"], h["hd"], h["Hv"], h["dk"], h["dv"], h["K"], h["neg"]) \
        == (30, 128, 30, 96, 192, 4, True)
    hash(ref.hashable(config()))
    assert ref.period_runs(h["pattern"]) == (
        4, [("linear_attention", 0, 3), ("full_attention", 3, 4)])
    assert ref.period_runs(("full_attention",) * 5) == (
        1, [("full_attention", 0, 1)])
    with open(ref.__file__) as src:
        assert "paddle_tpu" not in src.read()
    with open(fam.__file__) as src:
        assert "paddle_tpu.models" not in src.read()


def test_family_imports_nothing_until_called():
    import subprocess
    code = ("import sys; import perfbench.families.olmo_hybrid as f; "
            "f.shapes({'family': 'olmo_hybrid', **__import__('json').load("
            "open('perfbench/configs/rehearse/olmo-hybrid-7b.l16.json'))}); "
            "sys.exit('jax' in sys.modules or 'paddle_tpu' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code],
                          cwd=hs.ROOT).returncode == 0


# ------------------------------------------------------------ the rehearsal

@pytest.fixture(scope="module")
def bench():
    return hs.load_cell(CELL, True)["bench"]


def more_requests(ctx):
    ctx["limits"] = dict(ctx["limits"], sample_requests=40)


def test_rehearsal_from_set_up_to_the_line_and_the_int8_control(bench, capsys):
    """Tiny float32 sizes: the program serves the reference's own tokens
    (gap 0), the int8 control reads thousandths (limit 3e-4 here)."""
    line = drive(CELL, seconds=1.5, control="int8", edit=more_requests)
    assert_line_shape(line, {m["name"] for m in bench["end_to_end"]})
    assert line["correct"] is True, line["compared"]
    assert line["control_correct"] is False, line["control_compared"]
    assert set(line["metrics"]) == {"rehearsal.output_tokens_per_s",
                                    "rehearsal.setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    info = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith('{"window_s"')][0]
    tiny = hs.load_cell(CELL, True)["cfg"]
    held = info["live_kv_bytes_at_close"]
    assert held == 0 or held >= fam.state_bytes(tiny, 4)


def test_altered_token_is_not_correct():
    line = drive(CELL, seconds=1.0, fault="altered_token")
    assert line["correct"] is False
    assert not line["compared"]["served_logit_gap_max"]["ok"]


def test_traced_rehearsal_prints_counters_only(bench):
    line = drive(CELL, seconds=1.0, trace=True)
    assert_line_shape(line, {m["name"] for m in bench["per_layer"]})
    assert set(line["metrics"]) == {"rehearsal.engine.slot_occupancy"}
    assert line["correct"] is True, line["compared"]


def test_the_cells_entries():
    cell = hs.load_cell(CELL, False)
    e = cell["traffic"]["engine"]
    assert (e["max_batch"], e["max_len"], e["page_size"], e["burst"]) \
        == (24, 3456, 16, 8)
    assert e["prompt_buckets"] == [2048, 3072] and e["page_buckets"] == [216]
    assert e["pool_hbm_bytes"] == 4_831_838_208          # ISSUE 30's 4.5 GiB
    assert cell["traffic"]["arrivals"] == {"process": "closed", "backlog": 8,
                                           "requests": 2048}
    assert cell["traffic"]["prompt_len"] == {"dist": "uniform", "lo": 1024,
                                             "hi": 3072}
    assert cell["traffic"]["output_len"] == {"dist": "uniform", "lo": 128,
                                             "hi": 384}
    names = {m["name"] for m in hs.metrics_of(cell["bench"], cell["cell"],
                                              "per_layer")}
    assert {"serve.device_share.gdn_step", "serve.device_share.gdn_scan",
            "serve.device_share.full_attn_read",
            "kernel.gdn_step_roofline.batch", "kernel.gdn_scan_roofline.batch",
            "serve.step_mfu.batch", "serve.work_roofline",
            "serve.device_share.prefill"} <= names
    assert "serve.device_share.kv_read" not in names    # one scopes list a cell
    lists = {json.dumps(hs.load_json("metrics", n + ".json")["args"]["scopes"])
             for n in names if n.startswith("serve.device_share.g")
             or n.endswith("full_attn_read")}
    assert len(lists) == 1


# ------------------------------------------ the scope-roofline reader

SERVE_TRACE = os.path.join(os.path.dirname(__file__), "fixtures",
                           "serve_tiny_named.xplane.pb")


def test_scope_roofline_on_a_recorded_trace(monkeypatch, capsys):
    """On the serving trace recorded on the chip (PR 27): the device
    seconds under a scope that is there, against the work a family counts
    for it; nothing for a scope the trace lacks, for a scope the family
    does not count, or for a family that counts no scope."""
    from perfbench import trace
    from perfbench.reducers import _program
    tr = trace.Trace(SERVE_TRACE)
    peaks = arith.load_peaks("TPU v5 lite")
    stub = types.ModuleType("perfbench.families.scoped")
    stub.scope_work = lambda cfg, scope, steps: \
        (2.0e6 * len(steps), 3.0e5) if scope in ("kv_read", "gdn_step") \
        else None
    monkeypatch.setitem(sys.modules, "perfbench.families.scoped", stub)
    env = {"trace": tr, "busy": tr.busy_seconds(), "xplane_path": SERVE_TRACE,
           "cfg": {"family": "scoped"}, "peaks": peaks, "traffic": {},
           "record": {"steps": [{}, {}, {}]}}
    seconds = scope_roofline.scope_seconds(_program.op_rows(env), "kv_read")
    assert seconds > 0
    least = max(6.0e6 / peaks["bf16_flops_per_s"],
                3.0e5 / peaks["hbm_bytes_per_s"])
    assert scope_roofline.read(env, "kv_read") == pytest.approx(
        100.0 * least / seconds)
    said = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert said["scope_roofline"] == "kv_read" and said["bound"] == "memory"
    assert scope_roofline.read(env, "gdn_step") is None     # not in the trace
    assert scope_roofline.read(env, "mlp") is None          # not counted
    env["cfg"] = config("rehearse/internlm2-1.8b.json")     # counts no scope
    assert scope_roofline.read(env, "kv_read") is None
    env["cfg"], env["peaks"] = {"family": "scoped"}, None   # a rehearsal
    assert scope_roofline.read(env, "kv_read") is None


# ------------------------------------------------- the closed loop's model

def test_loop_model_reads_the_mix_and_repeats_by_seed():
    """tools/loop_model.py: the part of the cell's spread that is the
    traffic's (PERF.md section 6, PR 30). No engine: the mix's lengths, a
    step of 21.2 ms, prefills of 0.105 / 0.19 s by bucket."""
    from perfbench.tools import loop_model
    mix = hs.load_json("traffic/longdoc-batch.json")
    a, b, c = (loop_model.rate(mix, s, 20.0, 0.0212, [0.105, 0.19])
               for s in (2147483401, 2147483401, 2147483402))
    assert a == b != c and 600 < a < 800 and 600 < c < 800
    # no prefill cost: a step makes 24 tokens, less the slots that end
    # inside a burst of 8 and idle to its end
    free = loop_model.rate(mix, 7, 20.0, 0.0212, [0.0, 0.0])
    assert 0.98 * 24 / 0.0212 < free < 24 / 0.0212
