"""1 - (union of the device's operation intervals) / traced window."""


def read(env):
    busy = env["busy"]
    if not busy:
        return None
    return 100.0 * (1.0 - busy[0] / busy[1])
