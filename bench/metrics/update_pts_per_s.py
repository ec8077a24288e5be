"""Points inserted plus points deleted per second, over whole committed
steps: from the window's start to the commit of the last step that
started inside it. Host clock."""

from bench import stats


def read(run):
    if not hasattr(run.loop, "step_records"):
        return None
    return stats.whole_step_rate(run.loop.t0, run.loop.seconds,
                                 run.loop.step_records())
