"""The control -- the plain reference put in the program's place and
computed with float8 matmul operands, the precision below the one each
configuration states -- fails the cell's limits; the reference against
itself passes them. At a size a CPU test can hold; the same readings at
the cells' own sizes come from `calibrate.py` on the chip."""
import pytest

import benchtest_util
import calibrate
from benchlib import compare


@pytest.mark.parametrize("cell", ["fleet-lenet5-n256",
                                  "lm-xlstm125m-4x2048"])
def test_control_fails_and_reference_passes(cell):
    w = benchtest_util.small_workload(cell)
    cfg, mod = benchtest_util.small_config(w["config"])
    for line in calibrate.readings(mod, cfg, w["traffic"], w["limits"],
                                   [2 ** 35 + 11]):
        failed = [k for k, v in line["control"].items()
                  if w["limits"][k] is not None and v > w["limits"][k]]
        assert failed, line["control"]
    data = mod.Data(cfg, w["traffic"], 7)
    ref = mod.Reference(cfg).run(data)
    checks = mod.compare_summaries(ref, ref, w["limits"])
    assert compare.all_within(checks)
