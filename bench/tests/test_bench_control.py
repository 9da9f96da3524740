"""The control of each cell comes out not correct: the plain reference
put in the program's place in bfloat16, the step below the float32 that
each configuration states, fails at least one of the cell's limits, while
the program passes them all.  Small sizes on the CPU; the readings at the
cells' own sizes on the chip are in PERF.md."""

import pytest

import _tiny
from bench import harness

CELLS = ["flatplate.capture", "resnet50.serve"]


def _calibrate(name):
    c = _tiny.cell(name)
    out = harness.runner(c).calibrate(_tiny.context(c, seed=2 ** 31 + 11),
                                       full=False)
    return harness.limits(c), out["program"], out["control_bfloat16"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_program_passes(name):
    limits, prog, ctl = _calibrate(name)
    assert all(v <= limits[k] for k, v in prog.items()), prog
    assert any(v > limits[k] for k, v in ctl.items()), ctl


def test_training_control_departs_from_the_reference():
    """The CPU computes each bfloat16 operation in float32 inside, so at a
    size a test run holds the training control stays under the limits
    (on the chip, at the cell's size, it reads 1.0 on the gradient and the
    change); here it departs from the reference by a hundred times the
    program's gap on every number."""
    limits, prog, ctl = _calibrate("quadconv_ae.train")
    assert all(v <= limits[k] for k, v in prog.items()), prog
    assert all(ctl[k] > 100 * max(v, 1e-9) for k, v in prog.items()), ctl
