"""Device time per kNN flush of the kNN program's ops other than the
Mosaic kernel: the frontier prep (grouping, packing, ordering) and the
rescore. Profiler trace; moves knn_p95_ms."""


def read(run):
    flushes = getattr(run.loop, "flush_count", {}).get("knn")
    if run.trace is None or not flushes:
        return None
    s = run.trace.device_s("knn", kernel=False)
    return None if s is None else 1e3 * s / flushes
