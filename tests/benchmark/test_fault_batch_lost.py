"""``correct`` has to come out false when half of the scan's batches are
lost under the server's plan: each cell's rehearsal, its tables written
in files of 4,096 rows, with the first half of its largest table's files
alone read (``faults.py``). The clean run on the same seed and arguments
is ``test_rehearsal.py::test_cell_rehearses_end_to_end``."""

import pytest

from faults import CELLS, check_not_correct


@pytest.mark.parametrize("fault", ["batch_lost"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_reads_not_correct(cell, fault, tmp_path):
    check_not_correct(cell, fault, tmp_path)
