"""The control comes out not correct where the program comes out correct.

The configurations state float32 activations with products at the TPU's
default precision, one bfloat16 pass.  The control is the reference put in
the program's place with the step below bfloat16 products: int8 operands
(each tensor scaled by its largest magnitude), which a v5e multiplies at
twice the bfloat16 rate.  On the same sampled boxes of one run, the
program has to read under the configuration's limit and the control over
it.  ``bench/control.py`` makes the same readings on the chip at a cell's
own size.
"""

import pytest

import bench_tiny_cell as tiny


@pytest.mark.parametrize("name", ["n337.bulk", "n337.roi"])
def test_int8_control_fails_where_the_program_passes(name):
    r = tiny.run(name, controls=("int8",))
    limit = r["checks"]["err_ratio"]["limit"]
    assert r["correct"] is True
    assert r["checks"]["err_ratio"]["value"] <= limit
    assert r["controls"]["int8"] > limit
