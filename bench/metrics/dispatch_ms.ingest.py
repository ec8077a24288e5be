"""Mean host time per ingest step to dispatch its delete and insert
(``SpatialServer.delete`` + ``insert``, before ``commit``). Harness
spans (host clock); moves update_pts_per_s."""


def read(run):
    steps = getattr(run.loop, "steps", None)
    if not steps:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1, _ in steps) / len(steps)
