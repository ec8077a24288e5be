"""Device time per update tick of the update programs on chip 0: each
tick's delete and insert over the shards, the all_to_all routing of
their rows included. The kNN and range-count programs queue behind
them on the device. Profiler trace; moves knn_p95_ms."""


def read(run):
    ticks = getattr(run.loop, "updates", None)
    if run.trace is None or not ticks:
        return None
    s = run.trace.device_s("update")
    return None if s is None else 1e3 * s / ticks
