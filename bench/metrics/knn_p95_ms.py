"""95th percentile of every kNN request due in the window, each timed
from its due time to its result on the host. Host clock."""

from bench import stats


def read(run):
    lat = run.loop.latencies_ms("knn") if hasattr(run.loop,
                                                  "latencies_ms") else []
    return stats.percentile(lat, 95) if len(lat) else None
