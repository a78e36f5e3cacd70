"""The trace reduction, on hand-made planes and on a small trace
recorded on a TPU v5e (``bench/testdata/v5e.xplane.pb``)."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parent / "testdata"


def _ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3)


def test_union_busy_ops_modules_and_gaps():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("a", 0, 100), _ev("b", 50, 100),
                                   _ev("a", 1000, 200)]),
        NS(name="XLA Modules", events=[_ev("jit_f", 0, 150),
                                       _ev("jit_g", 1000, 200)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[_ev("parse", 140, 900)])])
    cpu = NS(name="/device:CPU:0", lines=[
        NS(name="x", events=[_ev("c", 0, 5000)])])
    r = trace_reduce.reduce_planes([dev, host, cpu], window_s=0.002)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(350e-6)        # [0,150] + [1000,1200]
    assert r["window_s"] == 0.002
    assert r["op_s"] == pytest.approx({"a": 300e-6, "b": 100e-6})
    assert r["module_s"] == pytest.approx({"jit_f": 150e-6,
                                           "jit_g": 200e-6})
    assert r["idle_gaps"][0][0] == "parse"
    assert r["idle_gaps"][0][1] == pytest.approx(850e-6)


def test_recorded_v5e_trace():
    path = DATA / "v5e.xplane.pb"
    r = trace_reduce.reduce_dir(str(path))
    assert r["devices"] == 1
    assert "/device:TPU:0" in r["planes"]
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    assert sum(r["module_s"].values()) >= r["busy_s"] * 0.5
