"""--rehearse of the train runner at tiny sizes on the CPU: the line's
shape, the control, and `correct` false under each fault the cell can
have."""
import json

import numpy as np
import pytest

import perfbench.run as prun
from _drive import assert_line_shape, drive

CELL = "mistral-7b.train-packed-2k"


@pytest.fixture(scope="module")
def bench():
    return prun.hs.load_cell(CELL, True)["bench"]


def test_rehearsal_line_end_to_end(bench):
    line = drive(CELL)
    assert_line_shape(line, {m["name"] for m in bench["end_to_end"]})
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"rehearsal.train_tokens_per_s",
                                    "rehearsal.setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["compared"]["compilations_in_window"]["value"] == 0
    json.dumps(line)


def test_rehearsal_traced_line_has_no_device_metric(bench):
    line = drive(CELL, trace=True)
    names = {m["name"] for m in bench["per_layer"]}
    assert_line_shape(line, names)
    # on the CPU the trace has no TPU plane: every reader of the device
    # trace finds nothing and is left out; only the host clock's share stays
    assert set(line["metrics"]) == {"rehearsal.train.data_wait_share"}
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_main_prints_the_line_last(capsys, monkeypatch):
    import paddle_tpu.utils.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
    assert prun.main(["--workload", CELL, "--seed", str(2 ** 31 + 9),
                      "--seconds", "0.2", "--trace", "0", "--rehearse"]) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True and list(line)[-1] == "compared"
    assert "compared: loss_gap_step1" in out.err
    assert out.err.strip().splitlines()[-1].startswith("compared: ")


def test_no_tpu_is_an_error_outside_a_rehearsal(monkeypatch):
    import paddle_tpu.utils.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
    with pytest.raises(SystemExit) as e:
        prun.main(["--workload", CELL, "--seconds", "0.2"])
    assert "no TPU" in str(e.value)


def test_control_comes_out_not_correct():
    line = drive(CELL, control="int8")
    assert line["control_correct"] is False, line["control_compared"]
    assert "compared" not in line       # the program did not run


@pytest.mark.parametrize("fault,fails", [
    ("state_unchanged", {"grad_norm_gap", "change_norm_gap"}),
    ("half_batch", {"grad_norm_gap"})])
def test_fault_is_not_correct(fault, fails):
    line = drive(CELL, fault=fault)
    assert line["correct"] is False
    over = {k for k, r in line["compared"].items() if not r["ok"]}
    assert fails <= over, line["compared"]


def test_fault_by_name_on_the_command_line(capsys, monkeypatch):
    import paddle_tpu.utils.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
    assert prun.main(["--workload", CELL, "--seconds", "0.2", "--rehearse",
                      "--fault", "half_batch"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    with pytest.raises(SystemExit):     # a serving fault in a training cell
        drive(CELL, fault="altered_token")


def test_a_step_whose_rows_overlap_an_earlier_step_is_not_fresh():
    from perfbench import check, gen
    corpus = gen.synthetic_corpus(5000, 64, 3)      # a small vocabulary:
    T = 128                                         # runs of tokens repeat

    def batch(*starts):
        return (np.stack([corpus[s:s + T] for s in starts]),
                np.stack([corpus[s + 1:s + T + 1] for s in starts]))
    first, apart, over = batch(100, 3000), batch(1000, 4000), batch(2000, 160)
    for s, found in zip((100, 3000), check.row_starts(corpus, first[0])):
        assert s in found
    assert check.fresh_steps(corpus, [first, apart, over]) \
        == [True, True, False]
    # rows of one batch may overlap each other: nothing was learned between
    assert check.fresh_steps(corpus, [batch(100, 150), apart]) == [True, True]
    # T tokens and the label past them: a row that starts right there shares
    # the place of that label
    assert check.fresh_steps(corpus, [batch(100, 3000), batch(100 + T, 4000)]) \
        == [True, False]
    assert check.fresh_steps(corpus, [batch(100, 3000),
                                      batch(101 + T, 4000)]) == [True, True]


def test_later_losses_are_printed_with_their_freshness_not_compared(capsys):
    from perfbench import check, harness as hs
    limits = hs.load_cell(CELL, True)["limits"]["limits"]
    norms = {"grad_norm": {"a": 1.0}, "change_norm": {"a": 1.0}}
    checks = hs.Checks(limits)
    check.compare_training(checks, dict(norms, loss=[1.0, 2.0, 3.0]),
                           dict(norms, loss=[1.0, 1.0, 3.0]),
                           [True, False, True])
    assert set(checks.rows) == {"loss_gap_step1", "grad_norm_gap",
                                "change_norm_gap"} and checks.correct
    out = capsys.readouterr().out
    assert '"loss_gaps_by_step": [0.0, 1.0, 0.0]' in out
    assert '"fresh_batch_by_step": [true, false, true]' in out
    checks = hs.Checks(limits)
    check.compare_training(checks, dict(norms, loss=[1.1, 1.0, 3.0]),
                           dict(norms, loss=[1.0, 1.0, 3.0]), [True] * 3)
    assert not checks.rows["loss_gap_step1"]["ok"] and not checks.correct
