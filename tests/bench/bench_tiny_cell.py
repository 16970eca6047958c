"""A benchmark cell cut to a size a CPU test can hold.

The committed cells' configuration, mix and metric entries, with the net
shrunk to four feature maps (same layer pattern as a Table III net: conv,
pool, conv, pool, conv; overlap-save at the input, MPF pools, direct
convs) and the volumes to a few patches.  Everything else — the harness,
the generator, the reference, the limits — is the benchmark's own.
"""

import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import harness  # noqa: E402

NET = {"in_channels": 1,
       "layers": [["conv", 2, 4], ["pool", 2], ["conv", 3, 4], ["pool", 2], ["conv", 3, 3]]}
PLAN = {"prims": ["overlap_save", "mpf", "direct", "mpf", "direct"], "m": 1, "batch": 2}
# FOV 17, core 4: a 20³ input is one patch, a 24³ input eight (2 planes)
SIZES = {"n337.bulk": [[24, 24, 24]], "n337.roi": [[20, 20, 20], [24, 24, 24]]}
SEED = 3_000_000_019  # past 32 bits, as a benchmark run's seed may be


def cell(name: str) -> dict:
    c = harness.load_cell(name)
    c["config"] = dict(c["config"], net=NET, plan=PLAN, check={"boxes": 3, "edge": 4})
    c["traffic"] = dict(c["traffic"], sizes=SIZES[name], streams=[
        dict(s, rate_per_s=20.0) if s["loop"] == "open" else s for s in c["traffic"]["streams"]
    ])
    return c


def run(name: str, seed: int = SEED, seconds: float = 0.5, **kw) -> dict:
    from repro.core.hw import TPU_V5E

    return harness.run_cell(cell(name), seed, seconds, False, hw=TPU_V5E,
                            t_start=time.perf_counter(), **kw)
