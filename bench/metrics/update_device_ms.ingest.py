"""Device time per ingest step of the programs the step's dispatch
enqueued: the trees' delete and insert. Profiler trace; moves
update_pts_per_s."""


def read(run):
    steps = getattr(run.loop, "steps", None)
    if run.trace is None or not steps:
        return None
    s = run.trace.device_s("update")
    return None if s is None else 1e3 * s / len(steps)
