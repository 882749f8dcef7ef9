"""--rehearse of the serving runner (closed backlog and open loop) at tiny
sizes on the CPU, the control, and `correct` false when a token is altered
where it is produced. No cell of BENCHMARK.json is open-loop yet (PERF.md,
Open question 1): that path is driven with the batch cell's configuration
and limits under the mix of fixtures/open-loop.json."""
import json
import os

import pytest

import perfbench.run as prun
from _drive import assert_line_shape, drive

BATCH = "internlm2-1.8b.longctx-batch"
with open(os.path.join(os.path.dirname(__file__), "fixtures",
                       "open-loop.json")) as _f:
    OPEN_MIX = json.load(_f)


def open_loop(ctx):
    ctx["traffic"] = json.loads(json.dumps(OPEN_MIX))


@pytest.fixture(scope="module")
def bench():
    return prun.hs.load_cell(BATCH, True)["bench"]


def test_closed_backlog_rehearsal(bench):
    line = drive(BATCH, seconds=1.0)
    assert_line_shape(line, {m["name"] for m in bench["end_to_end"]})
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"rehearsal.output_tokens_per_s",
                                    "rehearsal.setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_open_loop_rehearsal(bench, capsys):
    line = drive(BATCH, seconds=1.5, seed=2 ** 31 + 77, edit=open_loop)
    assert line["correct"] is True, line["compared"]
    # every request due in the window was sent and answered; the tails are
    # over all of them (the line keeps only the metrics the cell lists)
    assert 12 <= line["attempted"] <= 50 and line["failed"] == 0
    info = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith('{"window_s"')][0]
    assert info["requests"] == line["attempted"]
    assert info["ttft_p50_ms"] > 0 and info["tpot_p50_ms"] > 0
    assert info["unfinished_after_drain"] == 0
    assert info["generator_lateness_ms"]["max"] >= 0


def test_engine_settings_pass_through_as_they_stand(monkeypatch):
    """A mix may set any option of the engine: none is picked by name."""
    import paddle_tpu.inference as inf
    from perfbench.families import llama
    seen = {}

    def engine(config, weights, **kw):
        seen.update(kw)
    monkeypatch.setattr(inf, "ContinuousBatcher", engine)
    cell = prun.hs.load_cell(BATCH, True)
    mix = dict(cell["traffic"], engine=dict(
        cell["traffic"]["engine"], prefix_cache_pages=64, top_k=None))
    llama.engine(cell["cfg"], mix, weights=None)
    assert seen["prefix_cache_pages"] == 64 and seen["temperature"] == 0.0
    assert seen["prompt_buckets"] == (16, 32) and seen["page_buckets"] == (4, 8)
    assert "top_k" not in seen and "pool_hbm_bytes" not in seen   # nulls
    assert seen["kv_layout"] == "paged" and seen["max_batch"] == 4


def test_traced_rehearsal_prints_counters_only(bench):
    line = drive(BATCH, seconds=1.0, trace=True)
    assert_line_shape(line, {m["name"] for m in bench["per_layer"]})
    # no TPU plane: no device metric
    assert set(line["metrics"]) == {"rehearsal.engine.slot_occupancy"}
    assert "busy_s" not in line["device"]


def more_requests(ctx):
    ctx["limits"] = dict(ctx["limits"], sample_requests=60)


def open_loop_more_requests(ctx):
    open_loop(ctx)
    more_requests(ctx)


@pytest.mark.parametrize("edit,seconds", [(more_requests, 1.5),
                                          (open_loop_more_requests, 3.0)],
                         ids=["closed", "open"])
def test_control_comes_out_not_correct(edit, seconds):
    """Tiny float32 sizes: the program reads 0, the int8 control some
    thousandths over a few hundred compared tokens (limit 3e-4 here)."""
    line = drive(BATCH, seconds=seconds, control="int8", edit=edit)
    assert line["correct"] is True, line["compared"]
    assert line["control_correct"] is False, line["control_compared"]


@pytest.mark.parametrize("edit", [None, open_loop], ids=["closed", "open"])
def test_altered_token_is_not_correct(edit):
    line = drive(BATCH, seconds=1.0, fault="altered_token", edit=edit)
    assert line["correct"] is False
    assert not line["compared"]["served_logit_gap_max"]["ok"]


def test_shed_request_is_failed_and_not_correct():
    line = drive(BATCH, seconds=1.0, fault="shed_request", edit=open_loop)
    assert line["failed"] == 1 and line["correct"] is False
    assert line["compared"]["failed_requests"]["value"] == 1


def test_sweep_tool_runs_several_rates_on_one_engine(capsys, monkeypatch):
    import paddle_tpu.utils.compile_cache as cc
    from perfbench.tools import sweep
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
    context = prun.context

    def open_context(*a, **kw):
        ctx = context(*a, **kw)
        open_loop(ctx)
        return ctx
    monkeypatch.setattr(prun, "context", open_context)
    sweep.main(["--workload", BATCH, "--rates", "10,30", "--seconds", "1",
                "--rehearse"])
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith('{"sweep_rate_per_s"')]
    assert [r["sweep_rate_per_s"] for r in rows] == [10.0, 30.0]
    assert rows[1]["requests"] > rows[0]["requests"] > 0
    assert all(r["failed"] == 0 for r in rows)
    assert all(r["e2e"]["ttft_p95_ms"] > 0 and r["e2e"]["tpot_p95_ms"] > 0
               for r in rows)
