"""95th percentile, over the requests due in the window, of the time each
spent in the engine's queue: its ``engine.queued`` span, from submission
to the tick that first popped one of its patches."""

from bench.metrics_common import quantile
from bench.program_spans import queue_waits


def read(run):
    return quantile(queue_waits(run), 0.95)
