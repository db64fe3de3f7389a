"""The control at a size a test run holds: the reference in bfloat16, put
in the program's place for the same window ticks, must fail the check
that the program passes."""

import pytest

from benchmark import control, harness
from test_harness_cpu import SEED


@pytest.mark.parametrize("workload", ["tiny_1chip.walk", "tiny_1chip.churn"])
def test_control_fails_where_the_program_passes(tiny, workload):
    cell = harness.load_cell(workload, tiny)
    engine = harness.build_engine(cell["config"], "pallas_interpret")
    cap = engine.params.capacity
    world, warm = harness.establish(engine, cell, SEED)
    ticks, _, _ = harness.drive(engine, world, lambda n, el: n >= 8,
                                harness._no_annotation)
    before = warm[-1]["epoch"]
    pick = harness.sample(ticks, SEED, cell["config"]["entities"])
    keys: dict = {}
    prog = harness.check(ticks, before, cap, pick, keys)
    ctrl = harness.check(control.control_ticks(ticks, before, cap, pick),
                         before, cap, pick, keys)
    assert all(prog[k] <= lim for k, lim in harness.LIMITS.items())
    assert ctrl["missing_pairs"] + ctrl["extra_pairs"] > 0
    assert ctrl["failed"] > 0
