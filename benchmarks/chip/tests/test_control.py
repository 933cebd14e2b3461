"""The comparison that decides ``correct`` fails its control: the plain
reference computed in float8 in the program's place, read at the same
positions of the same served requests, at smoke size on the CPU.  (On the
chip, at the cells' own sizes, ``control.py`` takes these readings.)"""
import jax

from benchmarks.chip import control
from benchmarks.chip.tests import smoke


def test_control_fails_where_the_program_passes():
    cell = smoke.cell()
    limit = cell.doc["limits"]["token_gap"]
    rows = control.readings(cell, jax.devices()[:1], [5, 2**31 + 9], 1.5)
    for row in rows:
        assert row["tokens_compared"] >= 40
        prog, ctrl = row["program"], row["control"]
        assert prog["correct"] and not ctrl["correct"], row
        assert ctrl["checks"]["token_gap"][1] == limit
        assert ctrl["checks"]["token_gap"][0] >= \
            3 * prog["checks"]["token_gap"][0], row
