"""A run with the timed path broken underneath reads `correct` false: one
case per fault the cell can have (one chip, so no exchange between
chips), and for the fleet a batch whose rows reach `classify` from the
wrong patients."""

from __future__ import annotations

import pytest

from bench.tests import rehearse

FAULTS = ["state_unchanged", "half_batch", "answer_altered"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", ["tiny_va.fleet", "tiny_lm.serve"])
def test_fault_is_not_correct(tmp_path, cell, fault):
    out = rehearse.run(cell, str(tmp_path), seed=5, seconds=1.5,
                       faults={fault: True})
    assert out["correct"] is False, out["checks"]


def test_rows_from_the_wrong_patients_are_not_correct(tmp_path):
    out = rehearse.run("tiny_va.fleet", str(tmp_path), seed=6, seconds=1.5,
                       faults={"rows_swapped": True})
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["va_wrong_share"]["value"] > 0.2
