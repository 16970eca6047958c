"""``direct_conv3d``'s share of its roofline in the n926 cell (k=9 convs):
Σ over its calls of max(ops / peak FLOP/s, bytes / peak B/s), over the
calls' device time."""

from bench import counts
from bench.metrics_common import roofline


def read(run):
    return roofline(run, "direct_conv3d", counts.direct_conv3d)
