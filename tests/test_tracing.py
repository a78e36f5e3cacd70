"""Spans and counters of the hub's request path (``repro.core.trace``):
self time of nested spans, per-lane recorders under concurrent ticks,
the edge's in-flight/idle accounting, compiles charged to the lane and
span they happen in, ``c3o.*`` events in a CPU profiler trace, and the
benchmark's seven readers of the ``/stats`` span totals."""
import asyncio
import glob
import importlib
import math
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import HubGateway, decode, encode
from repro.api.types import (ChooseRequest, LaneSnapshot, PredictRequest,
                             StatsResult)
from repro.core import trace
from repro.core.datastore import RuntimeDataStore
from repro.core.hub import Hub, JobRepo
from repro.serve.config_service import BatchLane
from repro.serve.edge import serve_edge
from repro.serve.loadgen import _request
from repro.workloads import spark_emul as W

CHOOSE = encode(ChooseRequest("grep", (15.0, 0.02),
                              t_max=400.0)).encode("ascii")
PREDICT = encode(PredictRequest("grep", "m5.xlarge",
                                ((4.0, 15.0, 0.02),))).encode("ascii")


def _rows(snapshot) -> dict:
    return {name: (count, total, own) for name, count, total, own
            in snapshot}


def _added(before, after) -> dict:
    """Counts added to each span name between two snapshots."""
    b = _rows(before)
    return {k: v[0] - b.get(k, (0,))[0] for k, v in _rows(after).items()
            if v[0] != b.get(k, (0,))[0]}


@pytest.fixture(scope="module")
def gw():
    hub = Hub()
    d = W.generate_job_data("grep")
    hub.publish(JobRepo("grep", "grep", d.schema,
                        RuntimeDataStore(d, seed=0),
                        model_names=["ernest", "bom"]))
    gw = HubGateway(hub, {m.name: m.price for m in W.MACHINES.values()},
                    (2, 4, 8))
    gw._service("grep")                    # fit outside the tests' windows
    return gw


# --------------------------------------------------------------------------
# the primitive
# --------------------------------------------------------------------------

def test_nested_spans_count_and_self_time():
    rec = trace.Recorder()
    with trace.recording(rec):
        with trace.span("outer", rows=3):
            time.sleep(0.02)
            for _ in range(2):
                with trace.span("inner"):
                    time.sleep(0.01)
        trace.interval("wait", 0.25)
    outside = trace.PROCESS.snapshot()
    with trace.span("elsewhere"):
        pass
    rows = _rows(rec.snapshot())
    assert set(rows) == {"outer", "inner", "wait"}
    n_out, tot_out, self_out = rows["outer"]
    n_in, tot_in, self_in = rows["inner"]
    assert (n_out, n_in) == (1, 2)
    assert tot_in == pytest.approx(self_in) and tot_in >= 0.02
    assert self_out == pytest.approx(tot_out - tot_in, abs=1e-12)
    assert 0.02 <= self_out < tot_out
    assert rows["wait"] == (1, 0.25, 0.25)
    # outside ``recording`` spans land on the process recorder
    assert _added(outside, trace.PROCESS.snapshot()) == {"elsewhere": 1}


def test_spans_crossing_awaits_in_tasks_sharing_a_stack():
    """Tasks that inherit one stack may interleave spans across awaits;
    each span still counts once and the stack unwinds to its root."""
    async def task(delay):
        with trace.span("outer"):
            await asyncio.sleep(delay)
            with trace.span("inner"):
                await asyncio.sleep(delay)

    async def drive():
        with trace.recording(trace.Recorder()) as rec:
            with trace.span("parent"):
                pass                       # the stack exists before gather
            await asyncio.gather(task(0.01), task(0.005), task(0.002))
            return rec, len(trace._stack())

    rec, depth = asyncio.run(drive())
    rows = _rows(rec.snapshot())
    assert (rows["outer"][0], rows["inner"][0], rows["parent"][0]) \
        == (3, 3, 1)
    assert depth == 1                      # only the root is left


def test_two_lanes_ticking_at_once_keep_their_own_totals():
    def dispatcher(spans_per_tick):
        def dispatch(contexts, t_max):
            for _ in range(spans_per_tick):
                with trace.span("engine.sync"):
                    pass
            return list(contexts[:, 0])
        return dispatch

    async def drive():
        a = BatchLane(dispatcher(1), tick_s=0.005, name="a")
        b = BatchLane(dispatcher(2), tick_s=0.005, name="b")
        a.start()
        b.start()
        try:
            got = await asyncio.gather(
                *(lane.submit((float(i),)) for i in range(6)
                  for lane in (a, b)))
        finally:
            await a.stop()
            await b.stop()
        return a, b, got

    before = trace.PROCESS.snapshot()
    a, b, got = asyncio.run(drive())
    assert sorted(got) == sorted(float(i) for i in range(6) for _ in "ab")
    for lane, per_tick in ((a, 1), (b, 2)):
        rows = _rows(lane.stats.spans.snapshot())
        ticks = lane.stats.batches
        assert rows["lane.wait"][0] == 6 == lane.stats.requests
        assert rows["lane.tick"][0] == ticks == rows["lane.pack"][0]
        assert rows["engine.sync"][0] == per_tick * ticks
    assert _added(before, trace.PROCESS.snapshot()) == {}


def test_new_shape_lowering_is_charged_to_its_lane_and_span():
    fresh = jax.jit(lambda x: x * 3.0 + 1.0)    # never lowered before

    def dispatch(contexts, t_max):
        with trace.span("engine.dispatch"):
            out = fresh(jnp.asarray(contexts, jnp.float32))
        return list(np.asarray(out)[:, 0])

    async def drive():
        lane = BatchLane(dispatch, name="fresh")
        lane.start()
        try:
            return lane, await lane.submit((1.0, 2.0))
        finally:
            await lane.stop()

    before = trace.PROCESS.snapshot()
    lane, got = asyncio.run(drive())
    assert got == 4.0
    rows = _rows(lane.stats.spans.snapshot())
    assert rows["engine.lower"][0] >= 1 and rows["engine.compile"][0] >= 1
    compiles = rows["engine.lower"][1] + rows["engine.compile"][1]
    _, total, own = rows["engine.dispatch"]
    assert own == pytest.approx(total - compiles, abs=1e-9)
    added = _added(before, trace.PROCESS.snapshot())
    assert "engine.lower" not in added and "engine.compile" not in added


# --------------------------------------------------------------------------
# the edge
# --------------------------------------------------------------------------

def test_edge_in_flight_and_idle_leave_out_stats_and_healthz(gw):
    async def drive():
        app, server = await serve_edge(gw)
        try:
            reader, writer = await asyncio.open_connection(server.host,
                                                           server.port)
            snaps = [trace.PROCESS.snapshot()]
            for method, path, body in (
                    ("POST", "/v1/choose", CHOOSE),
                    ("GET", "/stats", b""), ("GET", "/healthz", b""),
                    ("POST", "/v1/predict", PREDICT),
                    ("GET", "/stats", b"")):
                status, payload = await _request(reader, writer, method,
                                                 path, body)
                assert status == 200
                snaps.append(trace.PROCESS.snapshot())
            writer.close()
            return snaps, decode(payload.decode("utf-8")).result
        finally:
            await server.stop()

    snaps, stats = asyncio.run(drive())
    steps = [_added(a, b) for a, b in zip(snaps, snaps[1:])]
    for name in ("edge.in_flight", "edge.request.choose", "edge.decode",
                 "gateway.admit"):
        assert steps[0][name] == 1
    assert "edge.idle" not in steps[0]            # the first had none
    for step in (steps[1], steps[2], steps[4]):   # /stats, /healthz
        assert not any(k.startswith(("edge.in_flight", "edge.idle",
                                     "edge.request")) for k in step)
    assert steps[3]["edge.idle"] == 1             # closed by the predict
    assert steps[3]["edge.in_flight"] == 1
    assert steps[3]["edge.request.predict"] == 1
    # /stats serves the process recorder and each lane's own
    assert _rows(stats.spans)["edge.in_flight"][0] \
        == _rows(snaps[-2])["edge.in_flight"][0]
    lanes = {ln.lane: _rows(ln.spans) for ln in stats.lanes}
    assert lanes["grep"]["lane.tick"][0] == 1
    # a choose tick scores every machine's grid in one device program
    assert lanes["grep"]["engine.dispatch"][0] == 1
    assert lanes["grep"]["engine.sync"][0] == 1
    assert lanes["grep@m5.xlarge"]["engine.sync"][0] == 1


def test_profiler_trace_holds_the_leaf_spans_on_the_host_plane(gw,
                                                               tmp_path):
    from jax.profiler import ProfileData

    async def drive():
        app, server = await serve_edge(gw)
        try:
            reader, writer = await asyncio.open_connection(server.host,
                                                           server.port)
            for body in (CHOOSE, PREDICT):      # lanes and shapes warm
                path = "/v1/choose" if body is CHOOSE else "/v1/predict"
                await _request(reader, writer, "POST", path, body)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                for _ in range(3):
                    await _request(reader, writer, "POST", "/v1/choose",
                                   CHOOSE)
                    await asyncio.sleep(0.005)
            finally:
                jax.profiler.stop_trace()
            writer.close()
        finally:
            await server.stop()

    asyncio.run(drive())
    found = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = ProfileData.from_file(found[0]).planes
    names = {e.name for pl in planes if pl.name.startswith("/host:CPU")
             for ln in pl.lines for e in ln.events}
    want = {"edge.read", "edge.decode", "gateway.admit", "lane.pack",
            "hub.service", "engine.dispatch", "engine.sync",
            "service.select", "edge.encode", "edge.write", "edge.idle"}
    assert {trace.PREFIX + n for n in want} <= names


# --------------------------------------------------------------------------
# the benchmark's readers
# --------------------------------------------------------------------------

def _lane(name, spans):
    return LaneSnapshot(name, 0, 0, 0.0, math.nan, math.nan, math.nan,
                        spans)


def _stats(process, choose, predict):
    return StatsResult(0, 0, 0, False, math.nan, math.nan, math.nan,
                       (_lane("grep", choose), _lane("grep@m5.xlarge",
                                                     predict)), process)


#: /stats before and after a window: 8 decoded requests and 4 choose
#: ticks in it; set-up's fits and compiles before it
BEFORE = _stats(
    (("edge.decode", 2, 0.002, 0.002), ("edge.read", 4, 0.001, 0.001),
     ("edge.in_flight", 1, 0.5, 0.5), ("engine.cv", 15, 30.0, 20.0),
     ("engine.fit", 15, 2.0, 1.5), ("engine.lower", 90, 9.0, 9.0),
     ("engine.compile", 90, 4.0, 4.0)),
    (("lane.wait", 1, 0.001, 0.001), ("lane.tick", 1, 0.01, 0.01),
     ("engine.sync", 3, 0.003, 0.003)),
    (("lane.wait", 1, 0.5, 0.5), ("lane.tick", 1, 0.5, 0.5),
     ("engine.sync", 1, 0.5, 0.5)))
AFTER = _stats(
    (("edge.decode", 10, 0.010, 0.010), ("edge.read", 20, 0.005, 0.005),
     ("edge.encode", 8, 0.004, 0.004), ("edge.write", 8, 0.006, 0.006),
     ("edge.in_flight", 5, 2.5, 2.5), ("engine.cv", 15, 30.0, 20.0),
     ("engine.fit", 15, 2.0, 1.5), ("engine.lower", 92, 9.5, 9.5),
     ("engine.compile", 92, 4.5, 4.5)),
    (("lane.wait", 5, 0.003, 0.003), ("lane.tick", 5, 0.03, 0.03),
     ("engine.sync", 15, 0.007, 0.007)),
    (("lane.wait", 4, 9.0, 9.0), ("lane.tick", 4, 9.0, 9.0),
     ("engine.sync", 4, 9.0, 9.0)))
#: what each reader gives on them (the predict lane left out)
EXPECTED = {
    "edge_self_ms": 1e3 * (0.008 + 0.004 + 0.004 + 0.006) / 8,
    "lane_wait_ms": 1e3 * 0.002 / 4,
    "tick_host_ms": 1e3 * (0.02 - 0.004) / 4,
    "device_wait_ms": 1e3 * 0.004 / 4,
    "request_idle_share": 1.0 - 0.05 / 2.0,
    "setup_fit_s": 20.0 + 1.5,
    "setup_compile_s": 9.0 + 4.0,
}


def _ctx(before, after, traced=True):
    return SimpleNamespace(
        stats_before=before, stats_after=after,
        trace={"devices": 1, "busy_s": 0.05, "window_s": 20.0}
        if traced else None)


def _reader(name):
    root = str(Path(__file__).resolve().parents[1])    # holds bench/
    if root not in sys.path:
        sys.path.insert(0, root)
    return importlib.import_module(f"bench.metrics.{name}").read


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reads_the_window_from_stats(name):
    got = _reader(name)(_ctx(BEFORE, AFTER))
    assert got == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reads_none_without_spans(name):
    """A program without spans (its /stats has none) reads None, as does
    ``request_idle_share`` on an untraced run."""
    bare = StatsResult(0, 0, 0, False, math.nan, math.nan, math.nan,
                       (LaneSnapshot("grep", 1, 1, 1.0, 1.0, 1.0, 1.0),))
    assert _reader(name)(_ctx(bare, bare)) is None
    if name == "request_idle_share":
        assert _reader(name)(_ctx(BEFORE, AFTER, traced=False)) is None
