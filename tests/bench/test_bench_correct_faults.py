"""``correct`` comes out false when the timed path is broken underneath.

Each case drives a whole run of a tiny cell on the CPU (everything but the
harness's look for a chip) with one of ``bench/faults.py``'s faults planted
in the executor's patch step, and sees the comparison with the reference
refuse it: half of the batch left out, or an answer altered where it is
produced.  ``bench/control.py --fault`` makes the same readings on the chip
at a cell's own size.
"""

import pytest

import bench_tiny_cell as tiny
from bench import faults


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
@pytest.mark.parametrize("name", ["n337.bulk", "n337.roi"])
def test_broken_step_is_not_correct(name, fault):
    with faults.planted(fault):
        r = tiny.run(name)
    assert r["correct"] is False
    assert r["checks"]["err_ratio"]["value"] > r["checks"]["err_ratio"]["limit"]
