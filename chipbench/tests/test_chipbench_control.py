"""The control: the plain reference in fp8, put in the program's place,
fails the comparison that the program passes, at the smoke size on the
CPU (the cells' own readings on the chip are in PERF.md)."""
import pytest

from chipbench import harness
from chipbench.tests.smoke import smoke_cell


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_fp8_control_fails_the_limit(seed):
    c = harness.run_cell(smoke_cell(), seed, 1.5, False, require_chip=False,
                         log=lambda s: None, controls=("fp8",)).result["checks"]
    limit = c["widest_logit_gap"]["limit"]
    assert c["widest_logit_gap"]["value"] <= limit
    assert c["control_fp8_widest_logit_gap"]["value"] > limit
