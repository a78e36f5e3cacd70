"""Closed loop over bursts: ``burst`` chooses for one job go out at once,
one per connection, and the next burst leaves when the last answer of
this one is back.  Jobs follow a cycle of ``cycle`` bursts with Zipf
(``zipf_s``) counts, in the seed's order; each job's burst is planned
once and resent."""
from __future__ import annotations

import asyncio
import math

import numpy as np

from bench import data as D
from bench.loadgen import Req, Traffic


class Generator(Traffic):
    def __init__(self, cfg, mix, seed, seconds):
        super().__init__(cfg, mix, seed, seconds)
        counts = D.zipf_counts(mix["cycle"], len(self.jobs), mix["zipf_s"])
        cycle = self.rng.permutation(np.repeat(self.jobs, counts))
        self.templates = [[self.read("choose", str(job), math.nan)
                           for _ in range(mix["burst"])] for job in cycle]

    def connections(self) -> int:
        return self.mix["burst"]

    async def drive(self, loop, t0, conns):
        bursts, i = [], 0
        while (due := loop.time() - t0) < self.seconds:
            burst = [Req(r.op, r.job, r.body, due, context=r.context,
                         t_max=r.t_max)
                     for r in self.templates[i % len(self.templates)]]
            bursts.append(burst)
            await asyncio.gather(*(self.send(loop, t0, c, r)
                                   for c, r in zip(conns, burst)))
            i += 1
        self.reads = [r for b in bursts for r in b]
