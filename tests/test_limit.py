"""The per-test limit (helpers.time_limit, armed for every test by
conftest._limit): a wait past it fails by name with the stack of where
it waited, and a quick body leaves nothing armed behind."""

import signal
import time

import pytest

from tests.helpers import time_limit


def _disarmed():
    """Stand the suite's own limit down for this test (its fixture
    disarms again on the way out), so what is left armed is ours."""
    signal.setitimer(signal.ITIMER_REAL, 0)


def test_limit_fails_a_long_wait_with_its_stack():
    _disarmed()

    def waits_too_long():
        time.sleep(5)

    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as info:
        with time_limit(0.2):
            waits_too_long()
    assert time.monotonic() - t0 < 1
    assert "still waiting after 0.2 s" in str(info.value)
    assert "waits_too_long" in str(info.value)
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)


def test_limit_leaves_a_quick_body_alone():
    _disarmed()
    was = signal.getsignal(signal.SIGALRM)
    with time_limit(5, 10):
        assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= 5
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) is was
    time.sleep(0.01)    # nothing fires after the block
