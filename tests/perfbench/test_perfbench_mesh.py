"""The train runner's mesh path, rehearsed on four virtual CPU devices: a
four-chip cell is data (chips 4 and a mesh in the traffic mix), as PERF.md's
Open question 1 will add it."""
import jax
import pytest

from _drive import drive

CELL = "mistral-7b.train-packed-2k"


def as_four_chip_cell(ctx):
    ctx["cell"] = dict(ctx["cell"], chips=4)
    ctx["traffic"] = dict(ctx["traffic"],
                          mesh={"axes": ["dp", "tp"], "shape": [2, 2]})
    ctx["traffic"]["job"] = dict(ctx["traffic"]["job"], batch=4)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_mesh_rehearsal_is_correct_and_sharded(monkeypatch):
    from perfbench.families import llama
    seen = {}
    build = llama.train_step

    def spy(*a, **kw):
        step = build(*a, **kw)
        seen["devices"] = {d for d in step.params["wq"].sharding.device_set}
        seen["spec"] = str(step.params["wq"].sharding.spec)
        return step
    monkeypatch.setattr(llama, "train_step", spy)
    line = drive(CELL, edit=as_four_chip_cell)
    assert len(seen["devices"]) == 4 and "tp" in seen["spec"]
    assert line["correct"] is True, line["compared"]
    assert line["device"]["count"] == 4
    assert all(k.startswith("rehearsal.") for k in line["metrics"])


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_fewer_devices_than_the_cell_asks_for_is_an_error():
    from perfbench import harness as hs
    with pytest.raises(SystemExit):
        hs.require_chips({"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1}, 4, rehearse=False)
    with pytest.raises(SystemExit):
        hs.require_chips({"platform": "cpu", "kind": "cpu", "count": 8}, 1,
                         rehearse=False)
    hs.require_chips({"platform": "cpu", "kind": "cpu", "count": 8}, 4,
                     rehearse=True)
