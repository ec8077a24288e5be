"""Device time per range-count flush of the range-count program.
Profiler trace; moves range_p95_ms."""


def read(run):
    flushes = getattr(run.loop, "flush_count", {}).get("range_count")
    if run.trace is None or not flushes:
        return None
    s = run.trace.device_s("range")
    return None if s is None else 1e3 * s / flushes
