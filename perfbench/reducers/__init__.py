"""One small reader per kind of per-layer metric: read(env, **args) takes
the number from the runner's record (counters, the harness's clock) or from
the device trace, and returns None when it finds nothing to read."""
