"""How late the benchmark's own generator ran: sent minus due, 95th
percentile, in milliseconds."""

from harness import percentile


def read(run):
    v = run.readings.get("generator_lag_s")
    return 1e3 * percentile(v, 95) if v else None
