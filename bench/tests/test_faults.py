"""The harness, driven without its look for a chip at a size the host CPU
holds, sees ``correct`` come out false with the timed path broken
underneath: once for each fault a cell of its kind can have (one chip,
so no exchange between chips to leave out), and true without a fault.
A half batch left out of the refit is not among them: over a 150-step
cycle no compared number tells it from sound runs (PERF.md, section 2)."""
import pytest
import tiny

import faults

CASES = [
    ("tiny.small_open", "answer_altered"),
    ("tiny.bulk_closed", "answer_altered"),
    ("tiny.refit", "state_unchanged"),
]


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_sound_run_is_correct(tmp_path, cell):
    result = tiny.run(str(tmp_path), cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_caught(tmp_path, cell, fault):
    with faults.PLANTS[fault]():
        result = tiny.run(str(tmp_path), cell)
    assert not result["correct"], result["checks"]
