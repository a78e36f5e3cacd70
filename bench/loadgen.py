"""What every traffic generator shares: requests encoded with the wire
codec, the HTTP/1.1 exchange with the edge over localhost, and the
window's bookkeeping.

A mix file ``bench/traffic/<mix>.json`` names its generator module under
``generator``; ``load`` imports ``bench/traffic/<generator>.py`` and
builds its ``Generator`` (a ``Traffic``) from the mix, the seed and the
window length, so a new arrival process is a new module and a new mix a
new data file.  A generator plans its requests from the seed (every seed
the same amount of work, in its own order) and drives one window in
``drive``.

A request is timed from when it was due.  Bodies are encoded before the
window opens and answers decoded after it closes, so the generator's own
work in the window is the socket exchange alone.
"""
from __future__ import annotations

import asyncio
import importlib
import math
import time
from dataclasses import dataclass, field
from typing import List, Tuple

import numpy as np

from bench import data as D


@dataclass
class Req:
    op: str                    # choose | predict
    job: str
    body: bytes
    due: float                 # seconds after the window opened
    machine: str = ""
    context: tuple = ()
    t_max: float = math.nan
    sent: float = math.nan
    done: float = math.nan
    payload: bytes = b""
    result: object = None      # decoded answer (after the window)
    ok: bool = False
    extra: dict = field(default_factory=dict)


async def http(reader, writer, method: str, path: str,
               body: bytes = b"") -> Tuple[int, bytes]:
    """One HTTP/1.1 exchange on an open keep-alive connection (as
    ``repro.serve.loadgen._request``)."""
    writer.write((f"{method} {path} HTTP/1.1\r\nhost: edge\r\n"
                  "content-type: application/json\r\n"
                  f"content-length: {len(body)}\r\n\r\n").encode("ascii")
                 + body)
    await writer.drain()
    raw = await reader.readuntil(b"\r\n\r\n")
    lines = raw.decode("latin-1").split("\r\n")
    status = int(lines[0].split(" ")[1])
    length = 0
    for line in lines[1:]:
        k, _, v = line.partition(":")
        if k.strip().lower() == "content-length":
            length = int(v.strip())
    return status, (await reader.readexactly(length) if length else b"")


def _encode(obj) -> bytes:
    from repro.api import codec
    return codec.encode(obj).encode("ascii")


def choose_req(job, ctx, t_max, due) -> Req:
    from repro.api.types import ChooseRequest
    ctx = tuple(float(v) for v in ctx)
    return Req("choose", job, _encode(ChooseRequest(job, ctx, t_max=t_max)),
               due, context=ctx, t_max=t_max)


def predict_req(job, machine, row, due) -> Req:
    from repro.api.types import PredictRequest
    row = tuple(float(v) for v in row)
    return Req("predict", job, _encode(PredictRequest(job, machine,
                                                      (row,))),
               due, machine=machine, context=row)


def load(cfg: dict, mix: dict, seed: int, seconds: float) -> "Traffic":
    """The mix's generator, realised for one seed and window length."""
    module = importlib.import_module(f"bench.traffic.{mix['generator']}")
    return module.Generator(cfg, mix, seed, seconds)


class Traffic:
    """A mix, realised for one seed and one window length."""

    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.seconds = float(seconds)
        self.stores = D.job_data(cfg, seed)
        self.jobs = list(cfg["jobs"])
        self.rng = np.random.default_rng([seed, 7])
        self.reads: List[Req] = []

    def read(self, op, job, due) -> Req:
        """A single-context choose or a single-row predict for ``job``:
        a stored row of the job, its context jittered."""
        rng = self.rng
        store = self.stores[job]
        if op == "choose":
            x = D.sample_rows(store, 1, rng, self.mix["jitter"])[0]
            return choose_req(job, x[1:], D.deadline(rng, self.mix), due)
        machine = store.machines[int(rng.integers(len(store.machines)))]
        rows = D.sample_rows(store, 1, rng, self.mix["jitter"])
        return predict_req(job, machine, rows[0], due)

    # ------------------------------------------------ what a generator sets
    def connections(self) -> int:
        """Keep-alive connections the window uses."""
        raise NotImplementedError

    async def drive(self, loop, t0: float, conns) -> None:
        """Send the window's requests (``send``); ``reads`` holds every
        request of the window when it returns."""
        raise NotImplementedError

    # --------------------------------------------------------------- run
    async def run(self, port: int, on_open, on_close) -> dict:
        """Drive one window.  ``on_open`` is awaited just before the first
        request is due, ``on_close`` once every request of the window has
        its answer (so a trace stopped there cuts off none).  Returns the
        generator's own readings."""
        conns = [await asyncio.open_connection("127.0.0.1", port)
                 for _ in range(self.connections())]
        await on_open()
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        self.opened = time.perf_counter()
        try:
            await self.drive(loop, t0, conns)
            await on_close()
        finally:
            for _, w in conns:
                w.close()
        late = sorted(r.sent - r.due for r in self.all() if r.sent == r.sent)
        return {"late_p50_ms": 1e3 * _pct(late, 50),
                "late_p99_ms": 1e3 * _pct(late, 99),
                "late_max_ms": 1e3 * max(late, default=math.nan),
                "late_over_50ms": sum(x > 0.05 for x in late)}

    async def send(self, loop, t0, conn, req: Req) -> None:
        req.sent = loop.time() - t0
        try:
            _, req.payload = await asyncio.wait_for(
                http(*conn, "POST", f"/v1/{req.op}", req.body),
                self.seconds + 60.0)
            req.done = loop.time() - t0
        except (OSError, asyncio.TimeoutError,
                asyncio.IncompleteReadError) as e:
            req.extra["error"] = f"{type(e).__name__}: {e}"

    def all(self) -> List[Req]:
        return self.reads

    def decode(self) -> None:
        from repro.api import codec
        for r in self.all():
            if r.payload:
                try:
                    resp = codec.decode(r.payload.decode("utf-8"))
                except Exception as e:             # noqa: BLE001
                    r.extra["error"] = f"undecodable answer: {e}"
                    continue
                r.ok = bool(resp.ok)
                r.result = resp.result if resp.ok else None
                if not resp.ok:
                    r.extra["error"] = f"{resp.error_code}: {resp.detail}"


def _pct(values, p: float) -> float:
    from bench.metrics import percentile
    v = percentile(values, p)
    return math.nan if v is None else v
