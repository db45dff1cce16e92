"""The bytes a step requires, on a batch counted by hand."""
import numpy as np

from bench import work


def test_step_bytes_by_hand():
    ids = np.array([[5, 9, -1, -1],
                    [5, 7, 7, -1]], np.int32)
    # 5 live slots * 16 + 2 labels * 4 + 3 distinct ids (5, 7, 9) * 16
    assert work.step_bytes(ids) == 5 * 16 + 2 * 4 + 3 * 16


def test_empty_rows_cost_only_their_labels():
    assert work.step_bytes(np.full((3, 4), -1, np.int32)) == 12
