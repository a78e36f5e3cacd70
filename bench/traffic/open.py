"""Open-loop Poisson arrivals at ``rate_rps``: single-context chooses and
single-row predicts split by ``ops``, jobs by Zipf(``zipf_s``) over the
configuration's job order, on a pool of ``connections`` keep-alive
connections.  Every seed gets the same arrival gaps (drawn once from
``shape_seed``), op and job counts, in its own order."""
from __future__ import annotations

import asyncio

import numpy as np

from bench import data as D
from bench.loadgen import Traffic


class Generator(Traffic):
    def __init__(self, cfg, mix, seed, seconds):
        super().__init__(cfg, mix, seed, seconds)
        n = int(round(mix["rate_rps"] * self.seconds))
        gaps = np.random.default_rng(mix["shape_seed"]).exponential(1.0, n)
        gaps *= self.seconds * n / (n + 1) / gaps.sum()
        due = np.cumsum(self.rng.permutation(gaps)) - gaps.min()
        ops = self.rng.permutation(D.exact_split(n, mix["ops"]))
        counts = D.zipf_counts(n, len(self.jobs), mix["zipf_s"])
        jobs = self.rng.permutation(np.repeat(self.jobs, counts))
        self.reads = [self.read(str(o), str(j), float(t))
                      for o, j, t in zip(ops, jobs, due)]

    def connections(self) -> int:
        return self.mix["connections"]

    async def drive(self, loop, t0, conns):
        free: asyncio.Queue = asyncio.Queue()
        for c in conns:
            free.put_nowait(c)

        async def one(req):
            conn = await free.get()
            try:
                await self.send(loop, t0, conn, req)
            finally:
                free.put_nowait(conn)

        tasks = []
        for req in self.reads:
            delay = t0 + req.due - loop.time()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(loop.create_task(one(req)))
        await asyncio.gather(*tasks)
