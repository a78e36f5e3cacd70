"""Offered-load sweep of an open-loop mix: one server, one window per
rate, printing each window's tails.  It is how the rates in
``bench/traffic/*.json`` were found (PERF.md), not a benchmark run.

    python bench/sweep.py --workload paper-choose-steady --seed 11 \
        --seconds 8 --rates 25,50,100,200 [--trace-rate 100]

One JSON line per window goes to stdout, then the traced window's
reduction.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--trace-rate", type=float, default=0.0)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args(argv)
    env = run.child_env(os.environ, args.platform)
    os.environ["JAX_PLATFORMS"] = "cpu"
    cell = run.load_cell(args.workload)
    cfg = json.loads(cell.config_file.read_text())
    mix = json.loads(cell.mix_file.read_text())
    server = run.Server(cell, args.seed, bool(args.trace_rate),
                        args.platform, "", env)
    from bench import loadgen
    from bench.metrics import latencies, percentile
    try:
        ready = server.read("ready", run.READY_TIMEOUT_S)
        print(json.dumps({"ready": ready}), flush=True)
        rates = [float(r) for r in args.rates.split(",")]
        if args.trace_rate:
            rates.append(args.trace_rate)
        for i, rate in enumerate(rates):
            traced = bool(args.trace_rate) and i == len(rates) - 1
            t = loadgen.load(cfg, dict(mix, rate_rps=rate), args.seed + i,
                             args.seconds)
            t0 = time.perf_counter()
            r = asyncio.run(run.drive(server, ready["port"], t, traced))
            t.decode()
            ctx = type("C", (), dict(traffic=t, stats_before=r["before"],
                                     stats_after=r["after"]))
            from bench.metrics import choose_batch_mean
            row = {"rate": rate, "traced": traced, "wall_s":
                   time.perf_counter() - t0,
                   "n": len(t.all()), "failed": sum(not q.ok
                                                    for q in t.all()),
                   "choose_p50_ms": 1e3 * percentile(
                       latencies(ctx, ("choose",)), 50),
                   "choose_p99_ms": 1e3 * percentile(
                       latencies(ctx, ("choose",)), 99),
                   "predict_p99_ms": 1e3 * (percentile(
                       latencies(ctx, ("predict",)), 99) or 0.0),
                   "choose_batch_mean": choose_batch_mean.read(ctx),
                   **r["late"], "window": r["window"]}
            print(json.dumps(row), flush=True)
        server.send("finish")
        fin = server.read("finished", 600.0)
        if fin.get("trace"):
            tr = fin["trace"]
            summary = {k: tr[k] for k in ("devices", "planes", "busy_s",
                                          "window_s", "kernel_s",
                                          "device_ops", "idle_gaps",
                                          "module_s")}
            summary["op_names"] = list(tr["op_s"])[:60]
            print(json.dumps({"trace": summary}), flush=True)
    finally:
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
