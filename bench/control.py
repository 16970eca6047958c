#!/usr/bin/env python3
"""Readings for a cell's limits: the program and its controls, seed by seed.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11 12 13 \\
        --controls int8 bf16
    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11 12 13 \\
        --controls --fault half_batch

For each seed, one run of the cell as ``bench/run.py`` makes it (at the
cell's own sizes and load, over a window of ``--seconds``), and on the same
sampled boxes the controls: the reference put in the program's place at a
precision of ``bench/reference.py``.  One JSON line per seed, with the
program's number and each control's.  The lower reading of a limit is the
largest the program gives; the upper is the smallest a control gives.  With
``--fault`` the program runs with that fault of ``bench/faults.py`` planted
in its timed path, and has to read over the limit on every seed.

The benchmark's own runs never run this.  Like ``run.py``, it needs a TPU
and runs every seed in one process, so that compiled programs are shared.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import faults  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=["int8"])
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from repro.compile_cache import enable_compile_cache
    from repro.core.hw import profile_for_device_kind

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"control: needs a TPU, found platform {devices[0].platform!r}", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    hw = profile_for_device_kind(devices[0].device_kind)
    rows = []
    for seed in args.seeds:
        t = time.perf_counter()
        with faults.planted(args.fault) if args.fault else contextlib.nullcontext():
            r = harness.run_cell(
                cell, seed, args.seconds, False, hw=hw, t_start=t,
                devices=devices[: cell["chips"]], controls=args.controls,
            )
        row = {"seed": seed, "program": r["checks"]["err_ratio"]["value"],
               **r.get("controls", {}), "attempted": r["attempted"], "failed": r["failed"],
               "correct": r["correct"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "fault": args.fault,
               "program_max": max(r["program"] for r in rows),
               "program_min": min(r["program"] for r in rows)}
    for c in args.controls:
        summary[f"upper.{c}"] = min(r[c] for r in rows)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
