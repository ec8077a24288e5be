"""Device time per kNN flush of the collective ops (``all-gather``,
``all-reduce``, ``all-to-all``, ``collective-permute``, async halves
included) inside the kNN programs, on chip 0: the distributed merge of
a sharded index. Profiler trace; moves knn_p95_ms."""

import bisect

COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute")


def read(run):
    flushes = getattr(run.loop, "flush_count", {}).get("knn")
    if run.trace is None or not flushes:
        return None
    knn = sorted((m[1], m[1] + m[2]) for m in run.trace.modules
                 if m[0] == "knn")
    starts = [s for s, _ in knn]
    ns = found = 0
    for dev, name, start, dur, _ in run.trace.ops:
        if dev != 0 or not name.startswith(COLLECTIVES):
            continue
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < knn[i][1]:
            ns += dur
            found += 1
    return 1e3 * ns / 1e9 / flushes if found else None
