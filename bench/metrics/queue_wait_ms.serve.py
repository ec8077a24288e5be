"""Mean time a kNN request waits in the batcher: from its due time to
the start of the flush that carried it. Harness timestamps (host
clock); moves knn_p95_ms."""


def read(run):
    if not hasattr(run.loop, "queue_wait_ms"):
        return None
    wait = run.loop.queue_wait_ms("knn")
    return float(wait.mean()) if len(wait) else None
