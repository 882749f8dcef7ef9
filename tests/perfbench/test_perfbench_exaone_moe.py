"""The K-EXAONE family under perfbench/: its counts to the digit, the work by
step at fixed lengths, the reference, the cell's rehearsal from set-up to
the line with the int8 control and the planted fault coming out not
correct, the cell's entries, and the reader of the experts' load."""
import json
import os
import sys
from collections import namedtuple

import numpy as np
import pytest

from _drive import assert_line_shape, drive
from perfbench import arith, families, harness as hs
from perfbench.families import exaone_moe as fam
from perfbench.reducers import (moe_load_max_over_mean, serve_step_mfu,
                                serve_work_roofline)

CELL = "k-exaone-236b.reasoning-batch"
CONFIGS = os.path.join(hs.HERE, "configs")

ATTN = 2 * 6144 * 8192 + 2 * 6144 * 1024        # 113 246 208
EXPERT = 3 * 6144 * 2048                        # 37 748 736
DENSE = 3 * 6144 * 18432                        # 339 738 624
HEAD = 6144 * 19200                             # 117 964 800
OUTSIDE = 8 * ATTN + DENSE + 7 * (6144 * 128 + EXPERT) + HEAD


def config(rel="k-exaone-236b.l8e16.json"):
    with open(os.path.join(CONFIGS, rel)) as f:
        return json.load(f)


# ------------------------------------------------------ counts, to the digit

def test_parameters_cache_and_rings_are_issue_34s():
    cfg = config()
    assert families.of(cfg) is fam
    assert fam.attn_params(cfg) == ATTN == 113_246_208
    assert fam.expert_params(cfg) == EXPERT == 37_748_736
    assert fam.outside_params(cfg) == OUTSIDE == 1_633_419_264
    assert fam.held_expert_params(cfg) == 7 * 16 * EXPERT == 4_227_858_432
    small = 8 * (2 * 6144 + 2 * 128) + 6144 + 7 * 128
    assert fam.total_params(cfg) == OUTSIDE + 7 * 16 * EXPERT + HEAD + small \
        == 5_979_349_888                        # 11.96 GB in bf16
    # an expert layer on this chip, as the issue reckons it (755.8 M)
    assert ATTN + 6144 * 128 + EXPERT + 16 * EXPERT == 755_761_152
    assert fam.kv_bytes_per_token(cfg) == 2 * 2 * 8 * 128 * 2 == 8192
    assert fam.ring_row_bytes(cfg) == 4096
    assert fam.state_bytes(cfg) == 6 * 128 * 4096 == 3_145_728
    assert fam.held_bytes(cfg, 1000, 7) == 1000 * 8192 + 7 * 3_145_728
    assert fam.local_share(cfg) == 16 / 128
    # 64 tokens x 8 assignments over 128 experts: 4 a held expert a step
    assert 64 * 8 * fam.local_share(cfg) / 16 == 4.0
    assert fam.experts_reached(cfg, 0) == 0.0
    assert fam.experts_reached(cfg, 1) == pytest.approx(1.0)    # 16 x 8/128
    assert 15.7 < fam.experts_reached(cfg, 64) < 16.0
    assert fam.experts_reached(cfg, 2048) == pytest.approx(16.0)


def test_the_file_holds_the_catalogs_numbers():
    """Every number of the published config under the same key, but the
    six that `reduced` names; what the cut states beside them."""
    cfg = config()
    want = {"first_k_dense_replace": 1, "head_dim": 128, "hidden_size": 6144,
            "intermediate_size": 18432, "max_position_embeddings": 262144,
            "moe_intermediate_size": 2048, "n_group": 1, "topk_group": 1,
            "num_attention_heads": 64, "num_experts_per_tok": 8,
            "num_key_value_heads": 8, "num_shared_experts": 1,
            "rms_norm_eps": 1e-05, "routed_scaling_factor": 2.5,
            "sliding_window": 128}
    assert {k: cfg[k] for k in want} == want
    assert cfg["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}
    assert (cfg["scoring_func"], cfg["norm_topk_prob"], cfg["hidden_act"],
            cfg["model_type"], cfg["sliding_window_pattern"],
            cfg["tie_word_embeddings"]) == ("sigmoid", True, "silu",
                                            "exaone_moe", "LLLG", False)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types", "num_experts", "vocab_size",
                              "num_nextn_predict_layers"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"], pub["vocab_size"],
            pub["num_nextn_predict_layers"]) == (48, 128, 153600, 1)
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (8, 16, 19200, 0)
    assert cfg["layer_types"] == pub["layer_types"][:8] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert cfg["mlp_layer_types"] == pub["mlp_layer_types"][:8] \
        == ["dense"] + ["sparse"] * 7
    assert len(pub["layer_types"]) == len(cfg["sliding_windows"]) == 48
    assert (cfg["num_experts_routed"], cfg["experts_held_first"]) == (128, 0)
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert (cfg["qk_norm"], cfg["rope_layer_types"], cfg["norm_placement"]) \
        == ("per_head", ["sliding_attention"], "pre")
    assert {"qk_norm", "rope_layer_types", "norm_placement", "selection_bias",
            "shared_expert", "router", "multi_token_prediction", "values",
            "weights"} <= set(cfg["assumed"])
    for said in ("8 v5e chips share each layer", "experts 0-15 of 128",
                 "rows 0-19199"):
        assert said in cfg["deployment"]


def test_parameter_tree_is_the_programs_each_kind_on_its_own_axis():
    cfg = config()
    shp = fam.shapes(cfg)
    assert shp["wq"] == (8, 6144, 8192) and shp["q_norm"] == (8, 128)
    assert shp["w_gate"] == (1, 6144, 18432)
    assert shp["gate_w"] == (7, 6144, 128) and shp["gate_bias"] == (7, 128)
    assert shp["moe_w_gate"] == (7, 16, 6144, 2048)
    assert shp["moe_w_down"] == (7, 16, 2048, 6144)
    assert shp["shared_w_up"] == (7, 6144, 2048)
    assert shp["lm_head"] == (6144, 19200) and shp["ln2"] == (8, 6144)
    assert sum(int(np.prod(s)) for s in shp.values()) == 5_979_349_888
    assert fam.layer_axes("moe_w_gate", 4) == (1, 2, 3)
    assert fam.layer_axes("gate_bias", 2) == (1,)
    assert fam.layer_axes("lm_head", 2) is None
    # the program's own tree has the same leaves and shapes
    from paddle_tpu.inference.replica import _spec_config
    from paddle_tpu.models.llama import llama_init_params
    import jax
    tiny = config("rehearse/k-exaone-236b.l8e16.json")
    spec = _spec_config({"config": fam.model_spec(tiny, 64)})
    tree = jax.eval_shape(lambda: llama_init_params(spec))
    assert {k: v.shape for k, v in tree.items()} == fam.shapes(tiny)
    assert spec.state_bytes_per_request() == fam.state_bytes(tiny, 4)
    assert spec.held == (4, 4) and spec.num_experts == 16
    full = _spec_config({"config": fam.model_spec(cfg, 5120)})
    assert full.ring_shapes(64)["win_k"][0] == (64, 128, 8, 128)
    assert (full.num_kv_layers, full.num_sliding_layers,
            full.num_sparse_layers, full.held) == (2, 6, 7, (0, 16))
    assert full.state_bytes_per_request() == 3_145_728
    with pytest.raises(ValueError, match="per-head QK-norm"):
        fam.model_spec(dict(cfg, qk_norm="whole"), 5120)
    with pytest.raises(ValueError, match="no range of the router's 128"):
        fam.dims(dict(cfg, experts_held_first=120))


# --------------------------------------------------------- the work by step

def test_work_by_step_at_fixed_lengths():
    cfg = config()
    tok = 2.0 * OUTSIDE + 2.0 * 7 * 8 * (16 / 128) * EXPERT
    assert fam.token_flops(cfg) == tok
    pairs = 2000 * 128 - 128 * 127 / 2.0
    assert fam.window_pairs(cfg, 2000) == pairs
    assert fam.window_pairs(cfg, 100) == 100 * 101 / 2.0    # under the window
    flops, byts = fam.prefill_work(cfg, 2000)
    assert flops == (tok - 2.0 * HEAD) * 2000 + 2.0 * HEAD \
        + 4.0 * 64 * 128 * (2 * 2000 * 2001 / 2.0 + 6 * pairs)
    assert byts == pytest.approx(2 * (OUTSIDE + 7 * 16 * EXPERT)
                                 + 2000 * 8192 + 3_145_728, rel=1e-12)
    # a burst: 8 steps, 2 requests; the first has wrapped its rings, the
    # second has not
    decodes = [(499, 1), (9, 3)]
    flops, byts = fam.burst_work(cfg, 8, decodes)
    full = 500 + 10 + 11 + 12
    ring = 128 + 10 + 11 + 12
    assert fam.ring_rows_read(cfg, 499, 1) == 128
    assert fam.ring_rows_read(cfg, 126, 3) == 127 + 128 + 128
    assert flops == 4 * tok + 4.0 * 64 * 128 * (2 * full + 6 * ring)
    reached = 16 * (1 - (1 - 8 / 128) ** 0.5)           # 4 tokens / 8 steps
    assert byts == pytest.approx(
        8 * 2 * (OUTSIDE + 7 * EXPERT * reached) + (full + 4) * 8192
        + 6 * (ring + 4) * 4096, rel=1e-12)
    # the scopes
    steps = [{"prefills": [2000, 100], "decode_steps": 8, "decodes": decodes},
             {"prefills": [], "decode_steps": 8, "decodes": [(30, 8)] * 64}]
    f, b = fam.scope_work(cfg, "moe_experts", steps)
    landed = (2000 + 100 + 4 + 512) * 8 * (16 / 128)
    assert f == pytest.approx(7 * landed * 2.0 * EXPERT, rel=1e-12)
    visits = fam.experts_reached(cfg, 2000) + fam.experts_reached(cfg, 100) \
        + 8 * fam.experts_reached(cfg, 0.5) + 8 * fam.experts_reached(cfg, 64)
    assert b == pytest.approx(7 * (2 * EXPERT * visits + landed * 4 * 6144),
                              rel=1e-12)
    rows = ring + 64 * sum(range(31, 39))
    assert fam.scope_work(cfg, "win_read", steps) == (
        4.0 * 64 * 128 * 6 * rows, 6.0 * rows * 4096)
    assert fam.scope_work(cfg, "win_attn", steps) == (
        4.0 * 64 * 128 * 6 * (pairs + 100 * 101 / 2.0),
        6.0 * 2100 * 2 * 128 * (2 * 64 + 2 * 8))
    assert fam.scope_work(cfg, "mlp", steps) is None
    # the readers that ask by step take it as it stands
    peaks = arith.load_peaks("TPU v5 lite")
    record = {"steps": steps, "chips": 1}
    assert serve_step_mfu.window_flops(record, cfg) == pytest.approx(sum(
        fam.prefill_work(cfg, t)[0] for t in (2000, 100)) + sum(
        fam.burst_work(cfg, 8, s["decodes"])[0] for s in steps), rel=1e-14)
    assert serve_work_roofline.least_seconds(record, cfg, peaks) > 0
    # the issue's step: 64 slots of ~2.4 k rows read ~13 GB, 16 ms
    f, b = fam.burst_work(cfg, 1, [(2400, 1)] * 64)
    least, bound = arith.roofline_seconds(f, b, peaks)
    assert bound == "memory" and 12.9e9 < b < 13.2e9 and 0.0155 < least < 0.0165
    experts = 2 * 7 * EXPERT * fam.experts_reached(cfg, 64)
    assert 0.63 < experts / b < 0.66        # the experts are most of it


def test_training_is_arithmetic_only():
    cfg = config()
    assert fam.train_attention_calls(cfg, 2, 2048) == [
        ((2, 64, 8, 2048, 128), 8)]
    assert fam.train_flops_per_token(cfg, 2048) == \
        6.0 * (OUTSIDE + 7 * 8 * EXPERT) + 12.0 * 64 * 128 * (
            2 * 2049 / 2.0 + 6 * fam.window_pairs(cfg, 2048) / 2048)
    with pytest.raises(SystemExit, match="no training cell"):
        fam.train_step(cfg, {}, None, None)


# ------------------------------------------------------------ the reference

def test_reference_reads_the_file_and_imports_nothing_of_the_program():
    ref = fam.reference()
    assert ref.__name__ == "perfbench.ref.exaone_moe"
    h = dict(ref.hashable(config()))
    assert h["pattern"] == tuple(config()["layer_types"])
    assert h["ffns"] == ("dense",) + ("sparse",) * 7
    assert (h["H"], h["KV"], h["hd"], h["window"], h["k"], h["E"], h["held"],
            h["scale"], h["theta"], h["qk_norm"], h["rope_kinds"]) == (
        64, 8, 128, 128, 8, 128, (0, 16), 2.5, 1e6, "per_head",
        ("sliding_attention",))
    hash(ref.hashable(config()))
    with open(ref.__file__) as src:
        assert "paddle_tpu" not in src.read()
    with open(fam.__file__) as src:
        assert "paddle_tpu.models" not in src.read()


def test_reference_masks_the_window_and_rotates_window_layers_only():
    """The reference's own pieces at a size one can follow by hand."""
    import jax.numpy as jnp
    ref = fam.reference()
    cfg = dict(ref.hashable(config("rehearse/k-exaone-236b.l8e16.json")))
    D, T = 64, 20
    rng = np.random.RandomState(0)
    p = {"ln1": jnp.ones((1, D)), "q_norm": jnp.ones((1, 16)),
         "k_norm": jnp.ones((1, 16)),
         "wq": jnp.asarray(rng.randn(1, D, 64) * 0.1, jnp.float32),
         "wk": jnp.asarray(rng.randn(1, D, 32) * 0.1, jnp.float32),
         "wv": jnp.asarray(rng.randn(1, D, 32) * 0.1, jnp.float32),
         "wo": jnp.asarray(rng.randn(1, 64, D) * 0.1, jnp.float32)}
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    full = ref.attention(x, p, 0, "full_attention", cfg, ref.f32_dot)
    win = ref.attention(x, p, 0, "sliding_attention", cfg, ref.f32_dot)
    # under the window (8) both see the same keys, but only one rotates
    assert not np.allclose(full[:8], win[:8], atol=1e-4)
    # a change to token 0 reaches query 12 in the full layer only
    x2 = x.at[0].add(1.0)
    full2 = ref.attention(x2, p, 0, "full_attention", cfg, ref.f32_dot)
    win2 = ref.attention(x2, p, 0, "sliding_attention", cfg, ref.f32_dot)
    assert not np.allclose(full[12], full2[12], atol=1e-5)
    np.testing.assert_allclose(win[8:], win2[8:], atol=1e-6)
    assert not np.allclose(win[7], win2[7], atol=1e-5)  # 7 - 8 < 0 <= 7
    # the rotation keeps a head's norm
    r = ref.rotate(jnp.ones((T, 2, 16)), 1e6)
    np.testing.assert_allclose(jnp.sum(r * r, -1), 16.0, rtol=1e-5)


def test_edge_is_the_distance_to_the_other_side_of_the_selection():
    import jax.numpy as jnp
    ref = fam.reference()
    cfg = {"scoring": "sigmoid", "k": 2, "norm_topk": True, "scale": 2.5}
    logit = jnp.log(jnp.asarray([[0.8, 0.6, 0.5, 0.1]]) /
                    (1 - jnp.asarray([[0.8, 0.6, 0.5, 0.1]])))
    bias = jnp.asarray([0.0, 0.0, 0.03, 0.0])
    w, edge = ref.route(logit, jnp.eye(4), bias, cfg)
    # selected: 0.8 and 0.6; the best left out is 0.5 + 0.03
    np.testing.assert_allclose(w[0], [2.5 * 0.8 / 1.4, 2.5 * 0.6 / 1.4, 0, 0],
                               rtol=1e-5)
    np.testing.assert_allclose(edge[0], [0.27, 0.07, 0.07, 0.5], atol=1e-6)


def test_undecided_positions_are_not_compared():
    """`served_logits` returns a pick AT the best logit where some sparse
    layer's selection is decided by less than the file's margin for a held
    expert; with the margin 0 (the rehearsal's file) every position is
    compared, and the real file says 0.008 with its reason."""
    import jax.numpy as jnp
    from perfbench.weights import make_weights
    ref = fam.reference()
    tiny = config("rehearse/k-exaone-236b.l8e16.json")
    assert tiny["decided_selection_margin"] == 0.0
    assert config()["decided_selection_margin"] == 0.008
    assert "decided_selection_margin" in config()["assumed"]
    w = make_weights(tiny, 5)
    toks = jnp.asarray(np.random.RandomState(5).randint(1, 256, 48), jnp.int32)
    picks = jnp.zeros(40, jnp.int32) + 7
    args = (w, toks, jnp.int32(7), picks)

    def gaps(margin):
        cfg = ref.hashable(dict(tiny, decided_selection_margin=margin))
        best, at, first = ref.served_logits(*args, cfg=cfg, dot="f32", n=40)
        return np.asarray(best - at), np.asarray(first)

    raw, first = gaps(0.0)
    assert (raw > 0).all()                  # token 7 is nowhere the best
    _, margins = ref.hidden(w, toks, ref.hashable(tiny), "f32")
    assert margins.shape == (tiny["mlp_layer_types"].count("sparse"), 48)
    least = np.asarray(margins.min(0))[7:47]
    cut = float(np.median(least))
    some, first2 = gaps(cut)
    assert 0 < (some == 0).sum() < 40
    np.testing.assert_array_equal(some == 0, least < cut)
    np.testing.assert_array_equal(some[least >= cut], raw[least >= cut])
    np.testing.assert_array_equal(first, first2)    # the best token stays
    assert (gaps(10.0)[0] == 0).all()


def test_family_imports_nothing_until_called():
    import subprocess
    code = ("import sys; import perfbench.families.exaone_moe as f; "
            "f.shapes(__import__('json').load(open("
            "'perfbench/configs/rehearse/k-exaone-236b.l8e16.json'))); "
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


def test_a_router_in_a_lower_precision_is_another_program():
    """The program's router is float32 and has no option. The benchmark's
    second control plants bfloat16 scores in its place
    (perfbench/tools/router_bf16.py): some selections change, and at these
    tiny float32 sizes the comparison sees it. A fixed set of requests, not
    a window: what a window finishes depends on the machine."""
    from perfbench import check
    from perfbench.tools import router_bf16
    from perfbench.weights import make_weights
    cell = hs.load_cell(CELL, True)
    cfg, weights = cell["cfg"], make_weights(cell["cfg"], 3)
    limit = cell["limits"]["limits"]["served_logit_gap_max"]

    def widest_gap():
        eng = fam.engine(cfg, cell["traffic"], weights)
        rng, prompts, out = np.random.RandomState(0), {}, {}
        for _ in range(8):          # the queue takes 4 x max_batch = 16
            for _ in range(12):
                p = rng.randint(1, cfg["vocab_size"],
                                rng.randint(8, 33)).tolist()
                prompts[eng.add_request(p, max_new_tokens=12)] = p
            out.update(eng.run())
        reqs = [{"prompt": prompts[r], "out": out[r]} for r in sorted(out)]
        return check.served_gaps(weights, cfg, reqs, pad_tokens=16,
                                 pad_outputs=8)["served"]

    with router_bf16.planted():
        planted = widest_gap()
    sound = widest_gap()
    # 0.0105 against 0.0 here; the rehearsal's limit is 3e-4
    assert planted > 10 * limit and sound <= limit


def test_traced_rehearsal_prints_counters_only(bench):
    line = drive(CELL, seconds=1.0, trace=True)
    assert_line_shape(line, {m["name"] for m in bench["per_layer"]})
    assert set(line["metrics"]) == {"rehearsal.engine.slot_occupancy"}
    assert line["correct"] is True, line["compared"]


def test_the_cells_entries():
    cell = hs.load_cell(CELL, False)
    e = cell["traffic"]["engine"]
    assert (e["max_batch"], e["max_len"], e["page_size"], e["burst"]) \
        == (64, 5120, 16, 8)
    assert e["prompt_buckets"] == [1024, 1536, 2048]
    assert e["page_buckets"] == [320]
    assert e["pool_hbm_bytes"] == 2_147_483_648          # ISSUE 34's 2 GiB
    # over the positions decided by more than the file's margin the program
    # read 0.042-0.100 on the chip, the int8 control 0.365-0.537 (PERF.md 2)
    assert cell["limits"]["limits"]["served_logit_gap_max"] == 0.2
    assert cell["cfg"]["decided_selection_margin"] == 0.008
    assert cell["traffic"]["arrivals"] == {"process": "closed", "backlog": 8,
                                           "requests": 2048}
    assert cell["traffic"]["prompt_len"] == {"dist": "uniform", "lo": 512,
                                             "hi": 2048}
    assert cell["traffic"]["output_len"] == {"dist": "uniform", "lo": 1024,
                                             "hi": 3072}
    assert cell["cell"]["chips"] == 1 and "4 tokens a held expert" in \
        cell["cell"]["why"]
    names = {m["name"] for m in hs.metrics_of(cell["bench"], cell["cell"],
                                              "per_layer")}
    new = {"serve.device_share.moe_experts", "serve.device_share.moe_router",
           "serve.device_share.win_read", "serve.device_share.global_read",
           "kernel.moe_experts_roofline.batch",
           "kernel.win_read_roofline.batch",
           "kernel.win_prefill_roofline.batch",
           "engine.moe_load_max_over_mean"}
    assert new | {"serve.step_mfu.batch", "serve.work_roofline",
                  "serve.device_share.prefill", "device.idle_share.batch",
                  "engine.slot_occupancy", "setup.init_s"} <= names
    assert "serve.device_share.kv_read" not in names    # one scopes list a cell
    lists = {json.dumps(hs.load_json("metrics", n + ".json")["args"]["scopes"])
             for n in names if n.startswith("serve.device_share.")
             and n != "serve.device_share.prefill"}
    assert len(lists) == 1
    # the new metrics are this cell's alone
    for m in cell["bench"]["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "output_tokens_per_s"


# ---------------------------------------------- the reader of the experts' load

Rec = namedtuple("Rec", "name args")


class Spans:
    def __init__(self, rows):
        self.rows = rows

    def inside(self, name):
        return [(0.0, 1.0, r) for r in self.rows if r.name == name]


def test_moe_load_reader_on_the_dispatch_spans(capsys):
    rows = [Rec("serve.dispatch_burst", {"kv_read": "kernel", "state": 1,
                                         "moe_local": 448, "moe_max": 40}),
            Rec("serve.dispatch_burst", {"kv_read": "kernel", "state": 1,
                                         "moe_local": 352, "moe_max": 35}),
            Rec("serve.dispatch_burst", {"kv_read": "kernel", "state": 0}),
            Rec("serve.admit", {"prefills": 1})]
    env = {"cfg": config(), "_program_spans": Spans(rows)}
    # 75 on the busiest of 16 experts that share 800: a mean of 50
    assert moe_load_max_over_mean.read(env) == pytest.approx(75 / 50)
    said = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert said["moe_assignments"] == {"bursts": 2, "on_held": 800,
                                       "on_busiest": 75, "held": 16}
    # a program without the arguments (the parent), or without spans
    old = [Rec("serve.dispatch_burst", {"kv_read": "kernel", "state": 0})]
    assert moe_load_max_over_mean.read(
        {"cfg": config(), "_program_spans": Spans(old)}) is None
    assert moe_load_max_over_mean.read(
        {"cfg": config(), "_program_spans": None}) is None
    assert moe_load_max_over_mean.read(
        {"cfg": {"family": "llama"}, "_program_spans": Spans(rows)}) is None


# ------------------------------------------------- the closed loop's model

def test_loop_model_reads_the_mix_and_repeats_by_seed():
    """tools/loop_model.py on this mix: a step of 22 ms, prefills of 0.06 /
    0.09 / 0.12 s by bucket (PERF.md section 6, PR 34)."""
    from perfbench.tools import loop_model
    mix = hs.load_json("traffic/reasoning-batch.json")
    a, b, c = (loop_model.rate(mix, s, 20.0, 0.022, [0.06, 0.09, 0.12])
               for s in (2147483401, 2147483401, 2147483402))
    assert a == b and 2400 < a < 2910 and 2400 < c < 2910
    free = loop_model.rate(mix, 7, 20.0, 0.022, [0.0, 0.0, 0.0])
    assert 0.98 * 64 / 0.022 < free < 1.0001 * 64 / 0.022
