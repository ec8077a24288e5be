"""Device time per kNN flush of the Mosaic frontier kernel
(``tpu_custom_call``). Profiler trace; moves knn_p95_ms."""


def read(run):
    flushes = getattr(run.loop, "flush_count", {}).get("knn")
    if run.trace is None or not flushes:
        return None
    s = run.trace.device_s("knn", kernel=True)
    return None if not s else 1e3 * s / flushes
