"""A whole run of each cell, with the chip lookup skipped and the model cut
to smoke size: a sound run comes out correct, and a run with the timed path
broken underneath (a token or an answer altered where it is produced) comes
out not correct, and so does a run in which a launch had to be retried."""
import time

import pytest

from bench.run import run_cell
from cells import CELLS, alter_tokens, cells_of, flaky_decode, shrink


def _run(cell, fault=None, trace=False):
    return run_cell(cell, 2**35 + 11, 1.5, trace, require_tpu=False, overrides=shrink,
                    fault=fault, t_start_ns=time.perf_counter_ns())


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = _run(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert "setup_s" in line["metrics"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answers_are_not_correct(cell):
    line = _run(cell, fault=alter_tokens)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("cell", cells_of("serve"))
def test_retried_launch_is_not_correct(cell):
    line = _run(cell, fault=flaky_decode)
    assert line["checks"]["launch_retries_in_window"]["value"] > 0
    assert not line["correct"], line["checks"]
