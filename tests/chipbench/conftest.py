"""Lays `stage_tables.py` into `test_chipbench_readers.py`'s hand-made
tables before its cases run (why it is done from here: `stage_tables.py`)."""

import pytest

from stage_tables import lay_into


@pytest.fixture(autouse=True, scope="module")
def stage_tables(request):
    module = request.module
    if module.__name__ == "test_chipbench_readers":
        lay_into(module.TURNS, module.WANT)
