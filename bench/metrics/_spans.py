"""Span totals served on ``GET /stats`` (``repro.core.trace``), for the
metrics that read them.

A snapshot's ``spans`` rows are ``(name, count, total_s, self_s)``: the
process's on the ``StatsResult`` itself, each lane's on its
``LaneSnapshot``.  A program without spans serves none, and every reader
here then gives an empty table, so the metrics read None.
"""
from __future__ import annotations

from typing import Dict, Tuple

#: name -> (count, total_s, self_s)
Table = Dict[str, Tuple[int, float, float]]


def table(rows) -> Table:
    return {name: (count, total, own) for name, count, total, own in rows}


def process(stats) -> Table:
    """The process recorder's totals (the edge, set-up, inline paths)."""
    return table(getattr(stats, "spans", ()))


def choose_lanes(stats) -> Table:
    """Totals summed over the choose lanes (predict lanes are named
    ``job@machine``)."""
    out: Dict[str, list] = {}
    for ln in stats.lanes:
        if "@" not in ln.lane:
            for name, count, total, own in getattr(ln, "spans", ()):
                row = out.setdefault(name, [0, 0.0, 0.0])
                row[0] += count
                row[1] += total
                row[2] += own
    return {k: tuple(v) for k, v in out.items()}


def delta(before: Table, after: Table) -> Table:
    """What was added between two snapshots."""
    zero = (0, 0.0, 0.0)
    return {k: tuple(a - b for a, b in zip(v, before.get(k, zero)))
            for k, v in after.items()}


def window(ctx, reader) -> Table:
    """``reader``'s totals added in the window (``/stats`` before and
    after it)."""
    return delta(reader(ctx.stats_before), reader(ctx.stats_after))


def count(t: Table, name: str) -> int:
    return t.get(name, (0, 0.0, 0.0))[0]


def total(t: Table, name: str) -> float:
    return t.get(name, (0, 0.0, 0.0))[1]


def own(t: Table, name: str) -> float:
    return t.get(name, (0, 0.0, 0.0))[2]
