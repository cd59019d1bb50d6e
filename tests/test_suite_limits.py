"""The suite's own time limit (``LIMIT`` in tests/conftest.py).

A pytest child runs a generated file under xdist with the conftest's limit
lowered to a few seconds, for that child only: the test that sleeps past it
fails by itself, with its stack in the output, and the test after it still
runs on a new worker.
"""

import os
import subprocess
import sys
import time

CONFTEST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "conftest.py")
LIMIT = 3

CHILD_CONFTEST = f"""
import importlib.util

_spec = importlib.util.spec_from_file_location("suite_conftest",
                                               {CONFTEST!r})
suite = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(suite)
suite.LIMIT = {LIMIT}
pytest_configure = suite.pytest_configure
pytest_runtest_call = suite.pytest_runtest_call
"""

CHILD_TESTS = """
import time


def test_sleeps_past_the_limit():
    with open({started!r}, "a") as f:
        f.write(f"{{time.time()}}\\n")
    time.sleep(60)


def test_after_it():
    with open({started!r}, "a") as f:
        f.write(f"{{time.time()}}\\n")
"""


def test_a_test_past_the_limit_fails_alone_with_its_stack(tmp_path):
    started = str(tmp_path / "started")
    (tmp_path / "pytest.ini").write_text("[pytest]\n")
    (tmp_path / "conftest.py").write_text(CHILD_CONFTEST)
    (tmp_path / "test_hang.py").write_text(CHILD_TESTS.format(
        started=started))
    t0 = time.monotonic()
    child = subprocess.run(
        [sys.executable, "-m", "pytest", "test_hang.py", "-p", "xdist",
         "-n", "1", "-p", "no:cacheprovider", "-p", "no:randomly",
         "-c", "pytest.ini", "--rootdir", str(tmp_path)],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    wall = time.monotonic() - t0
    out = child.stdout + child.stderr
    assert child.returncode == 1, out
    # every thread's stack, the sleeping test's frame among them
    assert f"Timeout (0:00:0{LIMIT})!" in out, out
    assert "in test_sleeps_past_the_limit" in out, out
    assert "node down" in out, out
    assert "1 failed, 1 passed" in out, out
    # the hung test took its limit and the next one started a few seconds
    # later, on the worker that took the crashed one's place
    first, after = (float(t) for t in open(started).read().split())
    assert LIMIT <= after - first < LIMIT + 30, (after - first, out)
    assert wall < 60, (wall, out)
