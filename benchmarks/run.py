"""Benchmark aggregator: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Roofline terms for the full
(arch x shape x mesh) grid come from the dry-run artifacts
(experiments/dryrun/*.json) and are summarized by `dryrun_summary`.
"""

from __future__ import annotations

import glob
import json
import os

from . import (
    fig2_pruned_fft,
    fig4_speedup,
    fig5_throughput,
    fig7_memory,
    table1_complexity,
    table2_memory,
    table4_primitives,
    table5_throughput,
)
from .common import emit

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")


def dryrun_summary() -> None:
    """Roofline terms per dry-run cell (the §Roofline table source)."""
    files = sorted(glob.glob(os.path.join(DRYRUN_DIR, "baseline__*.json")))
    if not files:
        emit("dryrun.summary", 0.0, "no dry-run artifacts; run repro.launch.dryrun")
        return
    for f in files:
        with open(f) as fh:
            rec = json.load(fh)
        cell = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}"
        if "skipped" in rec:
            emit(f"dryrun.{cell}", 0.0, "skipped")
            continue
        if "error" in rec:
            emit(f"dryrun.{cell}", 0.0, f"ERROR={rec['error'][:80]}")
            continue
        r = rec["roofline"]
        emit(
            f"dryrun.{cell}", 0.0,
            f"compute_s={r['compute_s']:.3e};memory_s={r['memory_s']:.3e};"
            f"collective_s={r['collective_s']:.3e};dominant={r['dominant']};"
            f"useful={r['useful_flops_ratio']:.3f}",
        )


def main() -> None:
    for mod in (
        fig2_pruned_fft,
        table1_complexity,
        table2_memory,
        table4_primitives,
        table5_throughput,
        fig4_speedup,
        fig5_throughput,
        fig7_memory,
    ):
        mod.main()
    dryrun_summary()


if __name__ == "__main__":
    main()
