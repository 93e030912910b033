"""The precision control at a size a test run holds: the plain reference
computed in int8 or fp8 in the program's place reads wider gaps than the
program does, and fails the limits that the program passes.

On the chip the same readings come from ``control.py`` at the cells' own
sizes; PERF.md gives them and the limits set between them.
"""
import pytest

import harness
from conftest import tiny_cell

SEEDS = (31, 32, 33)
# Set, as on the chip, between the program's widest reading and the fp8
# control's narrowest, on seeds 31-36 at this size (the configuration's
# weights, the same in every seed): the program's gap_error read
# 0.0040-0.0049, int8's 0.0084-0.0151, fp8's 0.054-0.056.
# The shortfall has no upper reading here: at a 512-token vocabulary the
# controls seldom change the first token.
LIMIT = 0.015


@pytest.fixture(scope="module")
def runs():
    cell, cfgs = tiny_cell("cascade-chat", limit=LIMIT)
    return [harness.run_cell(cell, s, 1.5, model_configs=cfgs,
                             controls=("int8", "fp8"),
                             say=lambda *a: None)
            for s in SEEDS]


def test_program_passes_and_fp8_control_fails(runs):
    """Held to the cell's limits by the harness itself: the program comes
    out correct, the fp8 control in its place not."""
    for out in runs:
        assert out["correct"] is True, out["checks"]
        assert out["control_correct"]["fp8"] is False


def test_controls_read_wider_than_the_program(runs):
    for out in runs:
        for stage in out["readings"].values():
            assert stage["gap_error"] < stage["int8.gap_error"] \
                < stage["fp8.gap_error"]
