"""The per-test time limit that conftest.py arms fires on a test that
runs past it."""
import time

import pytest


@pytest.mark.timeout(1)
def test_time_limit_fires(time_limit):
    assert time_limit == 1
    start = time.monotonic()
    with pytest.raises(TimeoutError):
        time.sleep(30)
    assert time.monotonic() - start < 5
