"""A run of 1,000 events of one key in one batch (test_dense_skew.py
holds the shorter runs and the helpers): every engine kind on one
device and one kind over the 4-device CPU mesh; a thousand rounds, each
stepped from the host."""

from __future__ import annotations

import pytest

from dense_layout_cases import ENGINES
from test_dense_skew import check_against_one_event_at_a_time


@pytest.mark.parametrize("eng_name,n_dev",
                         [(e, 1) for e in ENGINES] + [("every_r2", 4)])
def test_run_of_1000_equals_one_event_at_a_time(eng_name, n_dev):
    check_against_one_event_at_a_time(eng_name, n_dev, "run_of_1000")
