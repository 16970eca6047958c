"""95th percentile of request latency (due to complete), over every
request due in the window; a request never answered counts as answered at
the end of the grace period."""

from bench.metrics_common import latencies, quantile


def read(run):
    return quantile(latencies(run), 0.95)
