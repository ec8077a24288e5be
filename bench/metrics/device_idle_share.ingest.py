"""Share of the traced window in which no op ran on the device:
1 - (union of device op intervals / window). Profiler trace; moves
update_pts_per_s."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share()
