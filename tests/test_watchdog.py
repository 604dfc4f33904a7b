"""The per-test watchdog (``tests/conftest.py``): a test that runs past
its limit is reported failed by its own name, with its stacks dumped,
and the tests after it still run and count — under both xdist schedules
the suite is run with."""

import os
import subprocess
import sys
import xml.etree.ElementTree as ET

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))

THROWAWAY = '''
import time

import pytest


@pytest.mark.watchdog(1)
def test_sleeps_past_its_limit():
    time.sleep(5)


def test_passes():
    pass
'''


@pytest.mark.parametrize("dist", ["load", "loadfile"])
def test_overdue_test_fails_by_name_and_the_run_goes_on(tmp_path, dist):
    (tmp_path / "pytest.ini").write_text("[pytest]\n")
    (tmp_path / "test_throwaway.py").write_text(THROWAWAY)
    xml = tmp_path / "out.xml"
    env = dict(os.environ, PYTHONPATH=TESTS)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "conftest",
         "-p", "xdist", "-n", "1", "--dist", dist,
         "-p", "no:cacheprovider", "-p", "no:randomly",
         "--basetemp", str(tmp_path / "basetemp"),
         f"--junitxml={xml}", "test_throwaway.py"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=300)
    outcomes = {}
    for case in ET.parse(xml).iter("testcase"):
        failed = case.find("failure") is not None or \
            case.find("error") is not None
        assert case.get("name") not in outcomes, "reported twice"
        outcomes[case.get("name")] = "failed" if failed else "passed"
    assert outcomes == {"test_sleeps_past_its_limit": "failed",
                        "test_passes": "passed"}, proc.stdout
    assert proc.returncode == 1
    assert "FAILED test_throwaway.py::test_sleeps_past_its_limit" in \
        proc.stdout
    assert "in test_sleeps_past_its_limit" in proc.stderr  # the stacks
