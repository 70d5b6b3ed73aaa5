"""tools/test_budget.py on a small junit file: the sums per module, the
exit code at the 6% limit, and the seconds file that orders the run."""

import json
import os
import subprocess
import sys

import pytest

from tools import test_budget as TB


def _junit(path, cases):
    rows = "".join(
        f'<testcase classname="{c}" name="{n}" time="{t}"/>'
        for c, n, t in cases)
    path.write_text(
        '<?xml version="1.0" encoding="utf-8"?><testsuites><testsuite '
        f'name="pytest" errors="0" failures="0" skipped="0" '
        f'tests="{len(cases)}" time="1.0">{rows}</testsuite></testsuites>')
    return str(path)


# twenty modules of 5 s each are 5% of the total each; one more second on
# one of them and it is 5.9%, two more modules' worth and it is over
EVEN = [(f"tests.test_m{i:02d}", f"test_a[{j}]", 2.5)
        for i in range(20) for j in range(2)]


@pytest.mark.parametrize("extra,rc,over", [
    ([], 0, []),
    ([("tests.test_m00", "test_b", 1.0)], 0, []),
    # a class's cases are its module's
    ([("tests.test_m00.TestThing", "test_c", 1.5)], 1, ["tests/test_m00.py"]),
])
def test_sums_shares_and_exit_code(tmp_path, extra, rc, over):
    path = _junit(tmp_path / "junit.xml", EVEN + extra)
    by_module = TB.module_seconds(path)
    assert len(by_module) == 20
    want = 5.0 + sum(t for _, _, t in extra)
    assert by_module["tests/test_m00.py"] == (want, 2 + len(extra))
    assert next(iter(by_module)) == "tests/test_m00.py" or not extra
    assert TB.over_budget(by_module) == over
    run = subprocess.run([sys.executable, TB.__file__, path],
                         capture_output=True, text=True)
    assert run.returncode == rc, run.stdout + run.stderr
    assert f"{100.0 + want - 5.0:8.1f} s" in run.stdout.splitlines()[-1]
    assert ("OVER" in run.stdout) == bool(over)


def test_written_seconds_round_trip(tmp_path):
    path = _junit(tmp_path / "junit.xml", EVEN + [
        ("tests.test_m07", "test_b", 0.26)])
    by_module = TB.module_seconds(path)
    out = tmp_path / "module_seconds.json"
    TB.write_seconds(by_module, str(out))
    seconds = json.loads(out.read_text())
    assert list(seconds)[0] == "tests/test_m07.py"  # longest first
    assert seconds == {m: round(s, 1) for m, (s, _) in by_module.items()}
    assert seconds["tests/test_m07.py"] == 5.3
    # and the file the run is ordered from is such a file, of this tree
    with open(TB.SECONDS_FILE) as f:
        mine = json.load(f)
    root = os.path.dirname(os.path.dirname(TB.SECONDS_FILE))
    assert all(os.path.exists(os.path.join(root, m)) for m in mine), (
        "module_seconds.json names a module that is gone")
    assert list(mine.values()) == sorted(mine.values(), reverse=True)
