"""Share of the traced window in which no operation ran on the device, in
the n926 bulk cell: 1 - (union of device-op intervals) / window."""

from bench.metrics_common import idle_share


def read(run):
    return idle_share(run)
