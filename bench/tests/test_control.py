"""The control fails: the plain reference with every matrix product at
``high`` (three bfloat16 passes), put in the program's place, reads over
the cell's limits, while the program reads under them. At a size the
host CPU holds; ``calibrate.py`` reads the same on the chip at the
cells' own sizes."""
import pytest
import tiny

import calibrate
import run as bench_run
from harness import cells, check


@pytest.mark.parametrize("cell", ["tiny.bulk_closed", "tiny.small_open"])
def test_control_fails_the_limits(tmp_path, cell):
    bench, bench_dir = tiny.make(str(tmp_path))
    run = bench_run.Run(bench, cell, 11, 1.0, False, bench_dir, str(tmp_path), 0.0)
    run.world = cells.World(run.cfg, run.mix, run.seed)
    cells.load_kind(bench_dir, run.mix["kind"]).run(run)
    limits = check.load_limits(bench_dir, cell)
    assert check.judge(run.numbers, limits)[0], run.numbers
    control = calibrate.control_numbers(run)
    ok, checks = check.judge(control, limits)
    assert not ok, checks
