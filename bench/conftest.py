"""Fixtures of the benchmark's own tests (``python -m pytest bench``):
a small copy of the benchmark (two jobs, short windows) that a CPU can
run, with the limits of the real configurations."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))


def _small(root: Path) -> Path:
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)

    def load(p):
        return json.loads((BENCH / p).read_text())

    def dump(p, obj):
        (root / "bench" / p).write_text(json.dumps(obj))

    paper = dict(load("configs/c3o-paper.json"),
                 jobs={"sort": 126, "grep": 162})
    dump("configs/paper.json", paper)
    # the same hub on a seeded spot market: a deployment added as a file
    dump("configs/spot.json", dict(paper, jobs={"sort": 126}, market={
        "zones": ["az-1a", "az-1b", "az-1c"], "ticks": 64,
        "restart_overhead_s": 180.0}))
    steady = load("traffic/choose-steady.json")
    dump("traffic/steady.json", dict(steady, rate_rps=20, connections=8,
                                     warm_ticks=[1]))
    dump("traffic/burst.json", dict(steady, generator="burst", burst=8,
                                    cycle=3, ops={"choose": 1.0},
                                    warm_ticks=[8]))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"] = [
        {"name": "paper", "source": "test", "why": "test", "reduced": [],
         "file": "bench/configs/paper.json"},
        {"name": "spot", "source": "test", "why": "test", "reduced": [],
         "file": "bench/configs/spot.json"}]
    spec["workloads"] = [
        {"name": "paper-choose-steady", "config": "paper",
         "traffic": "steady", "chips": 1, "why": "test"},
        {"name": "spot-choose-burst", "config": "spot", "traffic": "burst",
         "chips": 1, "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="session")
def small_root(tmp_path_factory) -> Path:
    return _small(tmp_path_factory.mktemp("bench"))
