"""Median seconds of one batch's solve on the flusher thread
(`serve.device_solve_s` histogram of the service)."""

from harness import histogram


def read(run):
    return histogram(run, "serve.device_solve_s", "p50")
