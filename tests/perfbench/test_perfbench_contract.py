"""BENCHMARK.json against the contract's own rules, and the files that the
harness finds by the names in it."""
import importlib
import json
import os
import re

import pytest

import perfbench
from perfbench import harness as hs

ROOT = hs.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = 24      # what later PRs may add up to
    assert (2 + 14 * cells) * (BENCH["run_seconds"] + 60) + cells * 180 \
        + 1200 <= 43200
    assert all(isinstance(w, str) and not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(conf["name"]) and len(conf["why"]) <= 200
    assert any(conf["file"].startswith(p + "/") for p in BENCH["paths"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == conf["source"]
    assert cfg["reduced"] == conf["reduced"] and "assumed" in cfg
    assert not any(WIDTH.search(k) for k in conf["reduced"])
    assert not re.search(r"gpt-oss|gemma|llama|qwen3\.5", conf["source"],
                         re.I)
    assert any(w["config"] == conf["name"] for w in BENCH["workloads"])
    tiny = os.path.join(os.path.dirname(os.path.join(ROOT, conf["file"])),
                        "rehearse", os.path.basename(conf["file"]))
    assert os.path.exists(tiny)


def test_published_widths_are_as_issue_25_quotes_them():
    c = {x["name"]: json.load(open(os.path.join(ROOT, x["file"])))
         for x in BENCH["configs"]}
    i, m = c["internlm2-1.8b"], c["mistral-7b.l4"]
    assert (i["hidden_size"], i["intermediate_size"], i["num_hidden_layers"],
            i["num_attention_heads"], i["num_key_value_heads"],
            i["vocab_size"]) == (2048, 8192, 24, 16, 8, 92544)
    assert (m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["vocab_size"]) == (4096, 14336, 4, 32, 8, 32000)
    assert m["published"]["num_hidden_layers"] == 32
    assert i["rope_theta"] == m["rope_theta"] == 1e6
    assert m["sliding_window"] is None


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    real = hs.load_cell(cell["name"], rehearse=False)
    tiny = hs.load_cell(cell["name"], rehearse=True)
    kind = real["traffic"]["kind"]
    importlib.import_module(f"perfbench.runners.{kind}")
    assert real["limits"]["limits"]["compilations_in_window"] == 0
    assert tiny["cfg"]["hidden_size"] < real["cfg"]["hidden_size"]
    e2e = [m["name"] for m in hs.metrics_of(BENCH, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert hs.metrics_of(BENCH, cell, "per_layer")


def test_four_chip_cells_within_their_share():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_and_its_reader(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    reporting = {x["name"]: set(x.get("workloads", CELLS))
                 for x in BENCH["end_to_end"]}
    assert set(m["workloads"]) <= reporting[m["moves"]]
    spec = hs.load_json("metrics", m["name"] + ".json")
    reader = importlib.import_module(f"perfbench.reducers.{spec['reducer']}")
    assert callable(reader.read)
    if m["name"].endswith("_roofline") or "roofline" in m["name"] \
            or "mfu" in m["name"]:
        assert m["unit"] == "%" and m["source"] == "device_trace"


def test_names_are_unique_and_the_file_is_small():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_every_file_under_paths_has_a_plain_name():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel


def test_whole_step_mfu_beside_every_kernel_roofline():
    for m in BENCH["per_layer"]:
        if m["name"].startswith("kernel.") and "roofline" in m["name"]:
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in BENCH["per_layer"]), m["name"]


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_names_a_family_whose_module_exists(conf):
    from perfbench import families
    real = os.path.join(ROOT, conf["file"])
    tiny = os.path.join(os.path.dirname(real), "rehearse",
                        os.path.basename(real))
    for path in (real, tiny):
        with open(path) as f:
            cfg = json.load(f)
        fam = families.of(cfg)      # no default: the file says what it is
        assert fam.__name__ == "perfbench.families." + cfg["family"]
        assert os.path.exists(os.path.join(hs.HERE, "families",
                                           cfg["family"] + ".py"))
        for name in ("shapes", "GAINS", "engine", "train_step", "reference",
                     "layer_axes", "prefill_work", "burst_work",
                     "train_flops_per_token", "held_bytes",
                     "train_attention_calls"):
            assert hasattr(fam, name), name


def test_the_llamas_shape_is_named_in_its_two_files_only():
    """Everything under perfbench/ that knows a Llama's shape is behind
    families/llama.py and ref/llama.py: no other file imports the program's
    models, names its Llama classes or its engine, or imports the Llama
    reference (ISSUE 29, point 4)."""
    llama = re.compile(r"paddle_tpu\.models|LlamaConfig|LlamaTrainStep|"
                       r"shard_llama_params|ContinuousBatcher|llama_config|"
                       r"ref\.llama|ref import llama")
    seen = set()
    for d, dirs, files in os.walk(hs.HERE):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), hs.HERE)
            if not f.endswith(".py"):
                continue
            with open(os.path.join(d, f)) as src:
                if llama.search(src.read()):
                    seen.add(rel)
    assert seen == {os.path.join("families", "llama.py")}
    with open(os.path.join(hs.HERE, "ref", "llama.py")) as src:
        assert "paddle_tpu" not in src.read()       # nothing of the program


def test_harness_does_not_import_jax_at_import_time():
    import subprocess
    import sys
    code = ("import sys; import perfbench.harness, perfbench.arith, "
            "perfbench.gen, perfbench.stats, perfbench.trace, "
            "perfbench.families, perfbench.families.llama; "
            "perfbench.families.of({'family': 'llama'}); "
            "sys.exit('jax' in sys.modules or 'paddle_tpu' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code], cwd=ROOT).returncode == 0
    assert perfbench.__doc__
