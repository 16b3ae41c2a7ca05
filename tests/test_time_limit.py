"""The per-test time limit of the root conftest.py: a test that sleeps past
it fails by name, and the run goes on to the next test."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUITE = textwrap.dedent(
    '''
    import time

    import pytest

    import conftest


    @pytest.fixture(autouse=True)
    def short_limit(monkeypatch):
        monkeypatch.setattr(conftest, "TEST_TIME_LIMIT_S", 0.2)


    def test_sleeps_past_the_limit():
        time.sleep(60)


    def test_runs_after_it():
        pass
    '''
)


def test_sleeping_test_fails_by_name_and_the_run_goes_on(tmp_path):
    (tmp_path / "test_suite.py").write_text(_SUITE)
    out = subprocess.run(
        [
            sys.executable, "-m", "pytest", "-p", "conftest", "-q",
            "-p", "no:cacheprovider", "test_suite.py",
        ],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env={**os.environ, "PYTHONPATH": REPO},
    )
    assert out.returncode == 1, out.stdout + out.stderr
    assert "1 failed, 1 passed" in out.stdout, out.stdout
    assert (
        "test_suite.py::test_sleeps_past_the_limit ran past the time "
        "limit of 0.2 s"
    ) in out.stdout, out.stdout
