"""The control comes out not correct: the program with its own
reduced-precision path switched on, read by the cell's check at a tiny size
on the CPU, fails at least one of the cell's limits, while the sound program
passes them all; and the planted half-batch fault fails one too."""
import jax
import pytest

from chip import control, harness
from chip.tests import tiny

CELLS = {"production256-x8.train": "train",
         "cloverleaf1024-x64.mesh-train": "mesh-train"}


def failed(cell, readings):
    return [k for k, v in readings.items()
            if not harness.Check(k, v, float(cell.limits[k])).ok]


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_control_fails_and_sound_passes(cell_name):
    cell = tiny.run(CELLS[cell_name], cell_name,
                    ranks_checked_per_chip=2).cell
    (rec,) = control.control_readings(cell, [11], jax.devices()[:1])
    assert not failed(cell, rec["sound"]), rec
    assert failed(cell, rec["control"]), rec
    assert failed(cell, rec["half_batch"]), rec
