"""The control of each cell comes out as not correct: the plain reference
computed in bfloat16 (the precision below the configurations' float32),
put in the program's place, reads above the limit of at least one number
compared.  Tiny sizes on the CPU; the same readings at the cells' own
sizes come from ``chipbench/tools/readings.py`` on the chip."""

import pytest

from chipbench.tests import tiny
from chipbench.tools.readings import readings

CELLS = ["ludwig_lc_192.timesteps", "milc_wilson_32x64.solves"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_and_program_passes(workload):
    spec = tiny.spec(workload)
    limits = spec["config"]["check"]["limits"]
    (row,) = readings(workload, [7], [7], 1.0, spec=spec, require_tpu=False)
    assert all(row["program"][n] <= limits[n] for n in limits), row
    assert any(row["control"][n] > limits[n] for n in limits), row
