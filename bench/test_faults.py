"""A run with the timed path broken underneath must come out with
``correct`` false, once for each fault a cell can have
(``bench/faults.py``); the same run unbroken comes out true.  The look
for a chip is skipped, so this runs on the CPU at a small size."""
from __future__ import annotations

import io
import json
import sys

import pytest

from bench import run

CASES = {
    "sound": ("paper-choose-steady", ""),
    "runtime": ("paper-choose-steady", "runtime"),
    "tree": ("paper-choose-steady", "tree"),
    "rule": ("paper-choose-steady", "rule"),
    "half": ("spot-choose-burst", "half"),
}


def _run(root, cell, fault, seconds, seed=2**31 + 11):
    out, err = io.StringIO(), io.StringIO()
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0"], platform="any",
                  fault=fault, root=root, out=out, err=err)
    sys.stderr.write(err.getvalue()[-3000:])
    assert rc == 0, err.getvalue()[-3000:]
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("case", list(CASES))
def test_fault_turns_correct_false(small_root, case):
    cell, fault = CASES[case]
    # leaving the deadline out shows only on a choose whose deadline the
    # cheapest configuration misses, a few percent of them: a longer run
    line = _run(small_root, cell, fault, 10 if case == "rule" else 4)
    assert line["correct"] is (case == "sound"), line["checks"]
    assert list(line)[-1] == "checks"
