"""``os_segment``'s share of its roofline: Σ over its calls of
max(ops / peak FLOP/s, bytes / peak B/s), over the calls' device time."""

from bench import counts
from bench.metrics_common import roofline


def read(run):
    return roofline(run, "os_segment", counts.os_segment)
