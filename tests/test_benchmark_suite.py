"""The benchmark's own tests (``benchmark/tests``), counted case by case
under the tier-1 command.

They cannot simply be collected here: ``tests/conftest.py`` gives this
process four virtual CPU devices, and the benchmark's cells ask for exactly
the chips they name. So the whole of ``benchmark/tests`` runs once, in a
child with the plain CPU backend (``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests``, as ``benchmark/README.md`` says), and every case of it is
one case here that reads its outcome from the child's report.

The child spreads its cases over a few workers of its own: in one process
the suite takes 10 to 13 minutes alone and over 20 beside tier-1's other
workers, which was the whole run's longest pole and passed this module's
own limit (every case here then errors at once).
"""

import os
import subprocess
import sys
import xml.etree.ElementTree as ET

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITE = os.path.join("benchmark", "tests")
CHILD_WORKERS = 3


def _child(args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for key in ("XLA_FLAGS", "PYTEST_XDIST_WORKER", "PYTEST_XDIST_WORKER_COUNT",
                "PYTEST_CURRENT_TEST"):
        env.pop(key, None)
    return subprocess.run(
        [sys.executable, "-m", "pytest", SUITE, "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", *args],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=timeout)


def _collect():
    out = _child(["--collect-only", "-p", "no:xdist"], timeout=300).stdout
    return [line.strip() for line in out.splitlines()
            if line.startswith(SUITE.replace(os.sep, "/")) and "::" in line]


CASES = _collect()


@pytest.fixture(scope="module")
def outcomes(tmp_path_factory):
    """node id -> (outcome, message) from one run of the whole suite."""
    report = tmp_path_factory.mktemp("benchmark_suite") / "report.xml"
    proc = _child([f"--junitxml={report}", "-o", "junit_family=xunit1",
                   "-p", "xdist", "-n", str(CHILD_WORKERS)], timeout=1200)
    assert os.path.exists(report), proc.stdout[-3000:] + proc.stderr[-3000:]
    found = {}
    for case in ET.parse(report).getroot().iter("testcase"):
        node = f"{case.get('file')}::{case.get('name')}"
        bad = [c for c in case if c.tag in ("failure", "error", "skipped")]
        found[node] = (bad[0].tag, (bad[0].get("message") or "") + "\n" + (bad[0].text or "")) \
            if bad else ("passed", "")
    return found


def test_the_benchmark_has_its_cases():
    assert len(CASES) >= 60, CASES
    assert any("test_benchmark.py::test_a_cell_added_as_files_only" in c for c in CASES)
    assert any("test_scopes.py::" in c for c in CASES)


@pytest.mark.parametrize("case", CASES)
def test_benchmark_case(outcomes, case):
    outcome, message = outcomes.get(case, ("missing from the child's report", ""))
    assert outcome == "passed", f"{case}: {outcome}\n{message[-4000:]}"
