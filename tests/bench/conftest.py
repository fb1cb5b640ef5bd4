"""The checkout's root on the path for the benchmark's tests, and a tiny
cell under a temporary root."""
import sys

import pytest

from bench_testcells import ROOT, write_cell

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture
def tiny_root(tmp_path):
    return write_cell(tmp_path)
