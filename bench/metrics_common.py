"""Arithmetic the metric readers share."""

from __future__ import annotations

import statistics

from bench import counts, harness


def quantile(values, q: float):
    """The ``q`` quantile (inclusive method), or None without values."""
    if not values:
        return None
    if len(values) == 1:
        return values[0]
    cuts = statistics.quantiles(values, n=100, method="inclusive")
    return cuts[round(q * 100) - 1]


def latencies(run):
    """Seconds from due to done of every request due in the window; one
    never answered counts as answered when the grace period ended."""
    end = run.t_close + harness.GRACE_S
    return [
        (r.done if r.done is not None else end) - r.due
        for r in run.attempted() if r.due is not None
    ]


def idle_share(run):
    if not run.trace or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def roofline(run, kernel: str, count):
    """Share (%) of ``kernel``'s roofline over its traced calls, or None
    where the trace holds none of them.  ``count(operands, out)`` gives one
    call's (operations, bytes) from its shapes."""
    calls = (run.trace or {}).get("kernels", {}).get(kernel)
    if not calls:
        return None
    peaks = counts.peaks_for(run.device_kind)
    bound = spent = 0.0
    for dur_s, operands, out in calls:
        ops, nbytes = count(operands, out)
        bound += max(ops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
        spent += dur_s
    return 100.0 * bound / spent if spent > 0 else None
