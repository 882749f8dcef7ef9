"""Share of the window the train loop spent inside next(loader), on the
harness's clock."""


def read(env):
    rec = env["record"]
    return 100.0 * rec["data_wait_s"] / rec["window_s"]
