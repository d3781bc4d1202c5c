"""``correct`` has to come out false when one answer is altered where the
client receives it: each cell's rehearsal with its first timed reply
changed by the least a comparison can see (``faults.py``). The same run
with nothing broken is ``test_rehearsal.py::test_cell_rehearses_end_to_end``,
which reads ``correct`` true on the same seed and arguments."""

import pytest

from faults import CELLS, check_not_correct


@pytest.mark.parametrize("fault", ["answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_reads_not_correct(cell, fault, tmp_path):
    check_not_correct(cell, fault, tmp_path)
