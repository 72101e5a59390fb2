"""The per-test clock of tests/conftest.py, driven through a child pytest."""

import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

CONFTEST = """
import tests.conftest as real
real.TEST_LIMIT_S = 2
from tests.conftest import *  # the hooks, reading the patched limit
"""

TESTS = """
import asyncio, subprocess, sys

def test_busy_event_loop():
    async def main():
        while True:
            pass
    asyncio.run(main())

def test_blocked_subprocess_run():
    subprocess.run([sys.executable, "-c", "import time; time.sleep(120)"])

def test_the_next_one_still_runs():
    pass
"""


def test_clock_fails_a_hung_test_with_its_stack_and_goes_on(tmp_path):
    (tmp_path / "conftest.py").write_text(textwrap.dedent(CONFTEST))
    (tmp_path / "test_hangs.py").write_text(textwrap.dedent(TESTS))
    run = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-p", "no:xdist"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    out = run.stdout + run.stderr
    assert run.returncode == 1, out
    assert "2 failed, 1 passed" in out, out
    # each failure names the phase, the limit and where the main thread stood
    assert out.count("call still running after 2 s") == 2, out
    assert "in test_busy_event_loop" in out and "in main" in out, out
    assert "subprocess.py" in out and "in test_blocked_subprocess_run" in out, out
