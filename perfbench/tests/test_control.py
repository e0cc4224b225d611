"""The control, the reference one precision below the configuration's
(``high3``: three bf16 passes for float32 at ``highest``), fails each
cell's limit at the cell's own image size.  On the CPU, a few images per
cell; ``perfbench/control.py`` reads the same on the chip over whole pools."""
import jax
import pytest

from control import control_reading
from harness.spec import load_cell, load_limits
from test_spec import CELLS


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(cell):
    c = load_cell(cell)
    c.traffic = dict(c.traffic, pool_batches=1, batch=min(2, c.traffic["batch"]))
    reading = control_reading(c, 2**35 + 11, jax.devices()[:1])
    assert reading > float(load_limits(cell)["worst_rel_l2"])


@pytest.mark.parametrize("cell", CELLS[:1])
def test_reference_agrees_with_itself(cell):
    c = load_cell(cell)
    c.traffic = dict(c.traffic, pool_batches=1, batch=1)
    assert control_reading(c, 7, jax.devices()[:1], precision="highest") == 0.0
