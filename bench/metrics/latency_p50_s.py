"""Median request latency: from when a request was due to when its output
was complete, over every request due in the window."""

from bench.metrics_common import latencies, quantile


def read(run):
    return quantile(latencies(run), 0.5)
