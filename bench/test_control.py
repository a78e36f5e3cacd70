"""The lower-precision control (``bench/control.py``) must come out as
not correct: the engine's matrix products one step below the ``highest``
the configuration states.  A CPU computes f32 products exactly whatever
precision is asked, so the control means something on a TPU only; there
it runs the benchmark's small copy (``conftest.py``) end to end.

    python -m pytest bench/test_control.py     # on a machine with a TPU
"""
from __future__ import annotations

import io
import json
import os

import pytest

from bench import run


@pytest.fixture(scope="module")
def on_tpu() -> bool:
    """Looked at once, in a child (this process must not hold the chip),
    before any run: a server that has just exited may not have let the
    chip go yet."""
    import subprocess
    import sys
    out = subprocess.run([sys.executable, "-c",
                          "import jax; print(jax.devices()[0].platform)"],
                         capture_output=True, text=True,
                         env=dict(os.environ))
    return out.stdout.strip().endswith("tpu")


@pytest.mark.parametrize("precision", ["high", "bfloat16"])
def test_lower_precision_control_is_not_correct(small_root, on_tpu,
                                                precision):
    if not on_tpu:
        pytest.skip("the precision control acts on a TPU only")
    out, err = io.StringIO(), io.StringIO()
    rc = run.main(["--workload", "paper-choose-steady", "--seed",
                   str(2**31 + 5), "--seconds", "4", "--trace", "0"],
                  precision=precision, root=small_root, out=out, err=err)
    assert rc == 0, err.getvalue()[-3000:]
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is False, line["checks"]
