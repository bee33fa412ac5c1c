"""Shared fixtures of the benchmark's tests."""
import pytest

import benchtest_util  # noqa: F401  (puts the program on the path)


@pytest.fixture(autouse=True)
def span_store_off():
    """The fleet driver's readers turn the program's process-wide span
    store on as they are imported; stop it after each test, so no later
    test in the process records spans."""
    yield
    from repro.obs import trace
    stop = getattr(trace, "stop", None)
    if stop is not None:
        stop()
