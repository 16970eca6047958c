#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        from bench import harness
        from repro.compile_cache import enable_compile_cache
        from repro.core.hw import profile_for_device_kind
    except ImportError as e:
        print(f"bench: the system under test is missing here: {e}", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, found platform {devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    harness.log(f"[cache] compile cache at {enable_compile_cache()}")
    # cache every program, however quick to compile: a later run of the
    # cell then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    hw = profile_for_device_kind(devices[0].device_kind)
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), hw=hw, t_start=T_START,
        devices=devices[: cell["chips"]],
    )
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
