"""Shared by the rehearsal tests: drive a run past the look for a chip."""
import perfbench.run as prun

DEV = {"platform": "cpu", "kind": "cpu", "count": 1}


def drive(workload, seed=3, seconds=0.3, trace=False, control=None,
          fault=None, edit=None):
    """The rest of a run at the rehearsal's tiny sizes: the cell's runner,
    its comparison with the reference, and the result's line."""
    ctx = prun.context(workload, seed=seed, seconds=seconds, trace=trace,
                       rehearse=True, control=control, fault=fault)
    if edit is not None:
        edit(ctx)
    return prun.drive(ctx, dict(DEV, count=ctx["cell"]["chips"]))


def assert_line_shape(line, group_names):
    """The keys the driver reads, and nothing under a device metric's name."""
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "compared"
    assert line["rehearsal"] is True
    for name, m in line["metrics"].items():
        assert name.startswith("rehearsal."), name
        assert set(m) == {"value", "unit"}
        assert name[len("rehearsal."):] in group_names
    for row in line["compared"].values():
        assert set(row) == {"value", "limit", "ok"}
