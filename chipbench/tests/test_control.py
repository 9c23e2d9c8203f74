"""The control, the plain reference computed one precision step lower and put
in the program's place, fails the cell's limits where the program passes
them, at CPU size: held to the limits as a run holds them, the program's
verdict is correct and the control's is not."""
import pytest

from chipbench import control
from chipbench.tests.helpers import tiny_copy


@pytest.mark.parametrize("cell,number", [
    ("phi3.decode", "served_token_gap"),
    ("starcoder2.decode", "served_token_gap"),
])
def test_control_reads_above_the_program(tmp_path, cell, number):
    lines = control.main(["--workload", cell, "--seeds", "5-7",
                          "--control-seeds", "3", "--seconds", "0.05"],
                         root=tiny_copy(tmp_path), require_tpu=False)
    assert all(x["program"]["correct"] for x in lines), lines
    assert not any(x["control"]["correct"] for x in lines), lines
    program = max(x["program"][number] for x in lines)
    ctrl = min(x["control"][number] for x in lines)
    assert ctrl > 3 * program, (program, ctrl)
