"""95th percentile of a request's wait in the batcher's queue
(`serve.queue_wait_s` histogram of the service)."""

from harness import histogram


def read(run):
    return histogram(run, "serve.queue_wait_s", "p95")
